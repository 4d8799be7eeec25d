"""Model FLOP/s utilisation of the training window: forward and backward
operations per token from the configuration's shapes (no recomputation
counted), times tokens trained per second, over the chip's bf16 peak."""
from chipbench import flops


def read(run):
    x = run.extra
    if not x.get("tokens") or run.window_s <= 0:
        return None
    per_token = flops.train_flops_per_token(x["sizes"], x["seq"])
    rate = per_token * x["tokens"] / run.window_s
    return 100.0 * rate / run.peaks["bf16_flops_per_s"]

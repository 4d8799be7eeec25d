"""Model FLOP/s utilisation of an MoE training window: forward and backward
operations from the shapes (``chipbench/reference/deepseek_v2.py``, no
recomputation counted), the routed experts' share counted from the rows
the program sent them (``moe.routed_rows``), over the window's seconds and
the chip's bf16 peak."""
from chipbench.reference import deepseek_v2 as ref


def read(run):
    x = run.extra
    rows = run.counters.get("moe.routed_rows")
    if rows is None or not x.get("tokens") or run.window_s <= 0:
        return None
    work = ref.train_flops(x["sizes"], x["seq"], x["tokens"], rows)
    return 100.0 * work / run.window_s / run.peaks["bf16_flops_per_s"]

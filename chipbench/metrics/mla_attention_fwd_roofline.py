"""The Pallas causal attention forward (``kernels/flash_attention.py``) at
latent attention's head sizes (q and k of nope + rope, v of v_head_dim):
the least time of its calls over their summed device time in the trace,
in percent.  Bound by compute at these shapes."""
from chipbench import devtrace, flops
from chipbench.reference import deepseek_v2 as ref

KERNEL = "flash_attention_fwd"


def read(run):
    x = run.extra
    calls = devtrace.kernel_calls(run.device, KERNEL)
    if not calls or "moe_layers" not in x:
        return None
    work = ref.mla_attention_fwd(x["batch"], x["sizes"], x["seq"],
                                 x["itemsize"])
    least = flops.least_time(work, run.peaks)["seconds"]
    count, seconds = calls
    return 100.0 * least * count / seconds

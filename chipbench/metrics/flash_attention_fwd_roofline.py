"""The Pallas causal attention forward (``kernels/flash_attention.py``
through ``kernels/ops.py``): the least time of its calls over their
summed device time in the trace, in percent.  Causal attention at these
shapes is bound by compute."""
from chipbench import devtrace, flops

KERNEL = "flash_attention_fwd"


def read(run):
    x = run.extra
    calls = devtrace.kernel_calls(run.device, KERNEL)
    if not calls or "sizes" not in x:
        return None
    z = x["sizes"]
    work = flops.flash_attention_fwd(x["batch"], z["h"], z["kv"], x["seq"],
                                     z["hd"], x["itemsize"])
    least = flops.least_time(work, run.peaks)["seconds"]
    count, seconds = calls
    return 100.0 * least * count / seconds

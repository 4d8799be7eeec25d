"""The Pallas grouped matmul of the routed experts (``kernels/moe_gmm.py``
through ``kernels/ops.py``, forward calls only: the backward is XLA's):
the least time of its calls over their summed device time in the trace,
in percent.  The calls' rows are those the program computed (routed and
padding, from its counters); every forward pass makes three calls per
MoE layer and micro-batch, and passes are counted from the trace, so a
rematerialised forward counts as a pass."""
from chipbench import devtrace, flops
from chipbench.reference import deepseek_v2 as ref

KERNEL = "moe_gmm"


def read(run):
    x, c = run.extra, run.counters
    calls = devtrace.kernel_calls(run.device, KERNEL)
    if not calls or "moe.routed_rows" not in c or not run.units:
        return None
    count, seconds = calls
    per_pass = 3 * x["moe_layers"] * x["micro_batches"] * run.units
    rows = (c["moe.routed_rows"] + c.get("moe.gmm_pad_rows", 0)) \
        * count / per_pass
    work = ref.gmm_fwd(x["sizes"], rows, count, x["itemsize"])
    return 100.0 * flops.least_time(work, run.peaks)["seconds"] / seconds

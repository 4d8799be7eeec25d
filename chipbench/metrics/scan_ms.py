"""Host time of the ``study.scan`` span per study, in milliseconds: the
grid enumeration and the batched simulation (``dse/search.py``,
``dse/batched_sim.py``; its ``sweep`` child span is the same layer)."""


def read(run):
    ns = run.span_ns("study.scan")
    return ns / 1e6 / run.units if ns and run.units else None

"""Host time of the ``study.refine`` span per study, in milliseconds:
the refinement of the winners (``dse/search.py`` refine,
``core/network.py`` topology derivation; its ``refine`` child span is
the same layer)."""


def read(run):
    ns = run.span_ns("study.refine")
    return ns / 1e6 / run.units if ns and run.units else None

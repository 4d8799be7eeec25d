"""Host time of the ``study.keep`` span per study, in milliseconds: the
selection of the sweep's rows to keep, the top ``keep_top`` by
throughput and the Pareto pass over the feasible rows
(``api/study.py``, ``dse/pareto.py``)."""


def read(run):
    ns = run.span_ns("study.keep")
    return ns / 1e6 / run.units if ns and run.units else None

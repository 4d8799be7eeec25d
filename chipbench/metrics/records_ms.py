"""Host time of the ``study.records`` spans per study, in milliseconds:
the kept rows' records, the refined records, the ``StudyResult`` and its
frontier (``api/result.py``, ``api/study.py``).  The span opens before
and after the refinement; both are summed."""


def read(run):
    ns = run.span_ns("study.records")
    return ns / 1e6 / run.units if ns and run.units else None

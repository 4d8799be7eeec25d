"""Host time of the ``sim.device`` spans per study, in milliseconds: the
scan's jitted call on the chip, from the arguments' transfer in to the
outputs' copy back to numpy (``dse/batched_sim.py``)."""


def read(run):
    ns = run.span_ns("sim.device")
    return ns / 1e6 / run.units if ns and run.units else None

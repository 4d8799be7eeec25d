"""Programs traced inside the window by the scan's and the event
replay's jit caches (``jax_stats()["traces"]`` of ``dse/batched_sim.py``
and ``events/batch.py``)."""


def read(run):
    return run.counters.get("compiles")

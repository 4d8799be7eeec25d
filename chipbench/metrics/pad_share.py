"""Share of the rows sent to the chip by the scan that are padding, in
percent: each call's rows are edge-padded to a power-of-two bucket
(``dse/batched_sim.py`` ``_bucket``), so 100 * pad rows / (real rows +
pad rows) over the window."""


def read(run):
    c = run.counters
    if "batched_sim.jax_rows" not in c:
        return None
    pad = c.get("batched_sim.jax_pad_rows", 0)
    sent = c["batched_sim.jax_rows"] + pad
    return 100.0 * pad / sent if sent else None

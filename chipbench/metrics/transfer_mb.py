"""Bytes moved between host and chip by the scan's device calls per
study, in megabytes: the padded arguments in
(``batched_sim.h2d_bytes``) and the outputs back
(``batched_sim.d2h_bytes``)."""


def read(run):
    c = run.counters
    if "batched_sim.h2d_bytes" not in c or "batched_sim.d2h_bytes" not in c \
            or not run.units:
        return None
    total = c["batched_sim.h2d_bytes"] + c["batched_sim.d2h_bytes"]
    return total / 1e6 / run.units

"""Share of the rows the grouped-matmul kernel computes that are padding,
in percent: each held expert's rows are padded up to whole kernel blocks,
so 100 * ``moe.gmm_pad_rows`` / (``moe.routed_rows`` + pad rows) over the
window."""


def read(run):
    c = run.counters
    if "moe.routed_rows" not in c:
        return None
    pad = c.get("moe.gmm_pad_rows", 0)
    rows = c["moe.routed_rows"] + pad
    return 100.0 * pad / rows if rows else None

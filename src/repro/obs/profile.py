"""Kernel profiling harness — execution-grounded cost measurements.

Runs the repo's real kernels (``repro.kernels.ops``: flash attention
fwd+bwd, moe_gmm, ssd, rmsnorm, decode_attention) over an (M, N) shape
grid and reports, per measurement, the achieved FLOP/s and bytes/s
alongside the analytic FLOP/byte counts.  ``repro.calib`` fits the
analytic cost constants from these measurements — effective peak
FLOP/s, effective HBM bandwidth, and the ``M/(M+half)`` saturation
curves behind ``core/simulator._gemm_eff`` — and writes the
schema-versioned ``CALIB.json`` artifact the rest of the stack consumes
(``HW.calibrated``, ``Scenario.calibration``, ``cli calibrate``).

Every timed grid point runs under a ``profile.measure`` span and
samples the achieved rates onto the installed tracer as
``profile.achieved_tflops`` / ``profile.achieved_gbs`` gauge tracks, so
``cli calibrate --trace`` renders the whole grid as a Perfetto timeline
with counter tracks over it.

Each case times the kernels ``ops.default_backend()`` picks — the
Pallas kernels on a TPU, the xla (blockwise-jnp) path elsewhere — and
every row records that ``backend``.  On a CPU the absolute rates are
host numbers, but they saturate with M like the accelerator curves,
which is what the fit extracts.  jax and the kernel package are
imported lazily so ``repro.obs`` itself stays import-light.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from repro.obs import metrics
from repro.obs.trace import span

# kernels the harness knows how to drive, in measurement order
PROFILE_KERNELS = ("flash_attention_fwd", "flash_attention_bwd",
                   "moe_gmm", "ssd", "rmsnorm", "decode_attention")

# roofline regime each kernel's curve is fitted in (repro.calib):
# compute-bound kernels fit achieved FLOP/s, memory-bound kernels fit
# achieved bytes/s
KERNEL_KIND = {
    "flash_attention_fwd": "compute",
    "flash_attention_bwd": "compute",
    "moe_gmm": "compute",
    "ssd": "compute",
    "rmsnorm": "memory",
    "decode_attention": "memory",
}

_F32 = 4  # bytes per element; the harness measures in float32 throughout


def _grids(quick: bool) -> Dict[str, List[int]]:
    """M-axis grid per kernel (sequence length / rows / tokens /
    cache length).  ``quick`` drops the most expensive point and is the
    CI / ``--check`` grid — a strict prefix of the full grid so quick
    fits stay comparable to the committed full-grid artifact."""
    g = {
        "flash_attention_fwd": [128, 256, 512, 1024, 2048],
        "flash_attention_bwd": [128, 256, 512, 1024],
        "moe_gmm": [64, 128, 256, 512, 1024, 2048],
        "ssd": [128, 256, 512, 1024],
        "rmsnorm": [128, 512, 2048, 8192, 32768],
        "decode_attention": [512, 2048, 8192, 16384],
    }
    if quick:
        g = {k: v[:-1] for k, v in g.items()}
    return g


# N-axis grid (TP-sharded width) for the grouped matmul: fixed M, swept
# N — fits the ``N/(N+gemm_n_half)`` width-dimension curve
_MOE_N_GRID = [32, 64, 128, 256, 512]
_MOE_N_GRID_QUICK = [32, 64, 128, 256]


# ---------------------------------------------------------------------------
# Per-kernel workloads: build (jitted fn, args, flops, bytes, shape)
# ---------------------------------------------------------------------------
def _fa_case(s: int, bwd: bool):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    b, h, d = 1, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, s, d), jnp.float32)
    block = min(128, s)

    def fwd(q_, k_, v_):
        return ops.flash_attention(q_, k_, v_, causal=True, block=block)

    if bwd:
        # fwd + bwd in one call (the custom-VJP recompute path): the
        # scan path executes every (masked) block, so ~2.5x fwd work on
        # top of the fwd pass
        fn = jax.jit(jax.grad(lambda *t: fwd(*t).sum(), argnums=(0, 1, 2)))
        flops = 14.0 * b * h * s * s * d
    else:
        fn = jax.jit(fwd)
        flops = 4.0 * b * h * s * s * d
    bytes_ = _F32 * (4.0 * b * h * s * d) * (3.0 if bwd else 1.0)
    return fn, (q, k, v), flops, bytes_, {"b": b, "h": h, "s": s, "d": d}


def _moe_case(t: int, n: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    e, k = 4, 256
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (t, k), jnp.float32)
    w = jax.random.normal(ks[1], (e, k, n), jnp.float32) * 0.1
    # equal groups; every M on the grid is a multiple of 4 * 8, so each
    # group is whole token blocks of the Pallas kernel
    block_t = min(128, t // e)
    groups = [t // e] * e
    fn = jax.jit(lambda x_, w_: ops.moe_gmm(x_, w_, groups,
                                            block_t=block_t))
    flops = 2.0 * t * k * n
    bytes_ = _F32 * (t * k + e * k * n + t * n)
    return fn, (x, w), flops, bytes_, {"t": t, "e": e, "k": k, "n": n}


def _ssd_case(s: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    b, h, p, g, n = 1, 4, 32, 1, 32
    chunk = min(64, s)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bm = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    cm = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    fn = jax.jit(lambda *t: ops.ssd(*t, chunk=chunk))
    # order-of-magnitude analytic count (state outer products + intra-
    # chunk attention-like term); only this kernel's own curve uses it
    flops = b * s * h * (6.0 * p * n + 2.0 * chunk * p)
    bytes_ = _F32 * b * s * (2.0 * h * p + h + 2.0 * g * n)
    return fn, (x, dt, a, bm, cm), flops, bytes_, \
        {"b": b, "s": s, "h": h, "p": p, "n": n, "chunk": chunk}


def _rmsnorm_case(rows: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    d = 1024
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, d), jnp.float32)
    w = jnp.ones((d,), jnp.float32)
    fn = jax.jit(lambda x_, w_: ops.rmsnorm(x_, w_))
    flops = 4.0 * rows * d
    bytes_ = _F32 * (2.0 * rows * d + d)
    return fn, (x, w), flops, bytes_, {"rows": rows, "d": d}


def _decode_case(smax: int):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    b, hq, hkv, d = 1, 8, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, smax, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, smax, d), jnp.float32)
    pos = jnp.int32(smax - 1)
    fn = jax.jit(lambda q_, k_, v_, p_: ops.decode_attention(q_, k_, v_, p_))
    flops = 4.0 * b * hq * smax * d
    bytes_ = _F32 * (2.0 * b * hkv * smax * d + 2.0 * b * hq * d)
    return fn, (q, kc, vc, pos), flops, bytes_, \
        {"b": b, "hq": hq, "hkv": hkv, "smax": smax, "d": d}


def _cases(name: str, quick: bool):
    """(axis, x, builder()) tuples for one kernel's grid."""
    grid = _grids(quick)[name]
    if name == "flash_attention_fwd":
        return [("m", s, lambda s=s: _fa_case(s, bwd=False)) for s in grid]
    if name == "flash_attention_bwd":
        return [("m", s, lambda s=s: _fa_case(s, bwd=True)) for s in grid]
    if name == "moe_gmm":
        cases = [("m", t, lambda t=t: _moe_case(t, n=256)) for t in grid]
        n_grid = _MOE_N_GRID_QUICK if quick else _MOE_N_GRID
        cases += [("n", n, lambda n=n: _moe_case(512, n=n))
                  for n in n_grid]
        return cases
    if name == "ssd":
        return [("m", s, lambda s=s: _ssd_case(s)) for s in grid]
    if name == "rmsnorm":
        return [("m", r, lambda r=r: _rmsnorm_case(r)) for r in grid]
    if name == "decode_attention":
        return [("m", s, lambda s=s: _decode_case(s)) for s in grid]
    raise KeyError(f"unknown kernel {name!r}; known: "
                   f"{list(PROFILE_KERNELS)}")


# ---------------------------------------------------------------------------
# The harness
# ---------------------------------------------------------------------------
def profile_kernels(kernels: Optional[Sequence[str]] = None, *,
                    quick: bool = False,
                    reps: Optional[int] = None) -> List[dict]:
    """Measure every requested kernel over its (M, N) grid.

    Returns one measurement dict per grid point: ``{kernel, kind,
    backend, axis, x, shape, flops, bytes, time_s, flops_per_s,
    bytes_per_s, reps}``.
    Timing is best-of-``reps`` after a warm-up call (jit compile), via
    ``obs.bench.time_fn``.
    """
    from repro.kernels.ops import default_backend
    from repro.obs.bench import time_fn
    backend = default_backend()
    names = tuple(kernels) if kernels else PROFILE_KERNELS
    bad = sorted(set(names) - set(PROFILE_KERNELS))
    if bad:
        raise KeyError(f"unknown kernel(s) {bad}; known: "
                       f"{list(PROFILE_KERNELS)}")
    reps = reps if reps is not None else (2 if quick else 3)
    out: List[dict] = []
    for name in names:
        kind = KERNEL_KIND[name]
        with span("profile.kernel", kernel=name, kind=kind):
            for axis, x, build in _cases(name, quick):
                fn, args, flops, bytes_, shape = build()
                with span("profile.measure", kernel=name, axis=axis,
                          x=x, reps=reps):
                    t = time_fn(fn, *args, reps=reps, warmup=1)
                m = {"kernel": name, "kind": kind, "backend": backend,
                     "axis": axis, "x": int(x), "shape": shape, "flops": flops,
                     "bytes": bytes_, "time_s": t,
                     "flops_per_s": flops / t, "bytes_per_s": bytes_ / t,
                     "reps": reps}
                metrics.inc("profile.measurements")
                metrics.gauge("profile.achieved_tflops",
                              m["flops_per_s"] / 1e12)
                metrics.gauge("profile.achieved_gbs",
                              m["bytes_per_s"] / 1e9)
                out.append(m)
        metrics.inc("profile.kernels")
    return out

"""Vectorized batch replay: one wavefront over K records, numpy or jax.

``replay_batch`` replays many compiled ``StepProgram``s together, the
same discipline as ``repro.dse.batched_sim``: the recurrence advances in
static topological LEVELS of the step DAG, with one array operation per
level across ALL records — no per-record Python in the recurrence.
Node spans and the DP all-reduce use each program's steady-state rates
(every sibling flow active — the fair-share fixed point of a lockstep
schedule), so the batch path reproduces the scalar engine up to its
sub-node congestion dynamics (DP/HBM-relay sharing, OCS bank waits);
parity is pinned in tests/test_events.py.

The schedule structure is entirely static per (schedule, pp, v,
n_micro): ``_shape_tables`` compiles ``device_op_order`` +
``op_dependency`` once per shape into level-indexed integer tables.
Ops are layered by Kahn's algorithm over the op DAG (each device's
in-order slot chain plus the cross-device ``op_dependency`` edges), so
every dependency lands in a strictly earlier level and each (stage,
level) holds at most one op.  The tables, all ``(S, L)``:

  * ``ldir``    direction of the op a stage runs at each level
                (0=F, 1=B, -1=idle);
  * ``ldep_s``  the stage whose node END this op's START waits for
                (-1 = no cross dependency);
  * ``ldep_l``  the LEVEL that dependency completed at — the
                dependency-index table that makes chunk-wrapped
                ``interleaved`` deps as cheap as ``gpipe``'s monotone
                ones.

The recurrence is ``end[s, l] = max(dev_end[s], end[ldep_s, ldep_l])
+ tau`` — every schedule (``gpipe`` / ``1f1b`` / ``interleaved``) runs
through this one vectorized wavefront; there is no scalar fallback (the
``scalar_fallback`` output key is kept, always ``False``, for schema
stability).

Two backends for the recurrence (``backend=`` numpy|jax|auto).
``numpy`` loops the L levels in Python with (K, S) array ops per level
over the gathered per-record tables; records are processed in K-chunks
sized to ``NUMPY_CHUNK_BUDGET_BYTES`` of scratch — deep-pipeline shapes
(e.g. pp=16, v=2, nm=64) otherwise grow the per-record history past
the last-level cache and large K replays SLOWER than small K (the
one-time BENCH_events.json qwen3 anomaly: 3.6k rec/s at K=1024 vs
4.4k at K=64; chunked, rates are monotone in K).  ``jax`` goes further
than ``batched_sim``'s vmap-a-traced-function discipline: because the
tables are compile-time constants per shape key, ``_jax_shape_fn``
unrolls the whole recurrence AT TRACE TIME into a straight-line program
over (K,) vectors — no gathers, no carried history, no loop (a traced
``fori_loop`` over levels measures ~15x slower on CPU: XLA loop
overhead plus the O(S·L) carried history swamp the ~S flops per level).
Mixed-shape batches are grouped by shape key, one jit call per group;
each group's rows are edge-padded to the next power of two, so the jit
cache keys on (schedule, pp, v, n_micro, K-bucket) and a same-bucket
batch stream never re-traces — ``_JAX_TRACES`` counts traces exactly
like ``batched_sim._JAX_TRACES``.  ``auto`` picks jax at
``JAX_AUTO_MIN_RECORDS`` rows when jax imports.  This is what keeps
``Study.run(validate_top=K)`` and the outer search's fused per-round
event replay off the critical path.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.dse.batched_sim import _bucket
from repro.events.dag import StepProgram, device_op_order, op_dependency
from repro.obs import metrics

# below this many records the numpy level loop beats jax dispatch
# overhead; used by backend="auto" (the crossover is far lower than
# batched_sim's: one replay record is a whole schedule recurrence, not
# one closed-form expression).  The chunked numpy wavefront scales
# monotonically in K, so the crossover is K-independent and 32 holds
# across the bench shapes.
JAX_AUTO_MIN_RECORDS = 32

# per-chunk scratch budget for the numpy wavefront: float64 history +
# three gathered int32 tables ~ 20 bytes per (record, stage, level)
# cell.  Chunking K keeps the history resident in cache while the
# level loop sweeps it (see module docstring).
NUMPY_CHUNK_BUDGET_BYTES = 8 << 20

# incremented once per jax trace of a shape-keyed wavefront — the same
# contract as dse.batched_sim._JAX_TRACES (tests pin that a same-bucket
# batch stream does not grow it)
_JAX_TRACES = {"count": 0}


def jax_stats() -> Dict[str, int]:
    """Snapshot of the wavefront jit-cache internals: cumulative
    ``traces`` since process start and the ``auto`` crossover."""
    return {"traces": int(_JAX_TRACES["count"]),
            "auto_min_records": JAX_AUTO_MIN_RECORDS}


def resolve_backend(backend: str, n_records: int) -> str:
    """Map ``auto`` to a concrete wavefront backend for K records."""
    if backend == "auto":
        return "jax" if n_records >= JAX_AUTO_MIN_RECORDS else "numpy"
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}; "
                         f"use 'numpy', 'jax' or 'auto'")
    return backend


# ---------------------------------------------------------------------------
# Static shape tables: schedule structure compiled once per shape
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=512)
def _shape_tables(schedule: str, pp: int, v: int, nm: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(ldir, ldep_s, ldep_l), each (S, L) — see module docstring.

    Kahn layering: an op lands at level 1 + max(level of preds) where
    its preds are the previous slot on the same device and its
    ``op_dependency`` target.  Because the same-device chain is always
    an edge, levels are strictly increasing along each device's order,
    giving the at-most-one-op-per-(stage, level) property the dense
    recurrence relies on — and making the same-device predecessor
    always available as the running per-device end, so only the cross
    dependency needs an index.
    """
    orders = [device_op_order(schedule, pp, v, nm, s) for s in range(pp)]
    O = max(len(o) for o in orders)
    slot_of: Dict[Tuple[str, int, int, int], int] = {}
    for s, order in enumerate(orders):
        for i, (d, c, m) in enumerate(order):
            slot_of[(d, s, c, m)] = i

    dep_s = np.full((pp, O), -1, np.int32)
    dep_i = np.full((pp, O), -1, np.int32)
    for s, order in enumerate(orders):
        for i, (d, c, m) in enumerate(order):
            dep = op_dependency(d, s, c, m, pp, v)
            if dep is not None:
                dd, ds, dc, dm = dep
                dep_s[s, i] = ds
                dep_i[s, i] = slot_of[(dd, ds, dc, dm)]

    # Kahn layering over (in-order chain + cross-dep) edges
    def preds(s: int, i: int) -> List[Tuple[int, int]]:
        out = [(s, i - 1)] if i > 0 else []
        if dep_s[s, i] >= 0:
            out.append((int(dep_s[s, i]), int(dep_i[s, i])))
        return out

    n_ops = sum(len(o) for o in orders)
    indeg: Dict[Tuple[int, int], int] = {}
    succ: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
    for s, order in enumerate(orders):
        for i in range(len(order)):
            ps = preds(s, i)
            indeg[(s, i)] = len(ps)
            for p in ps:
                succ.setdefault(p, []).append((s, i))
    lvl = np.full((pp, O), -1, np.int32)
    q = deque(k for k, d in indeg.items() if d == 0)
    n_done = 0
    while q:
        s, i = q.popleft()
        n_done += 1
        lvl[s, i] = max((lvl[ps, pi] for ps, pi in preds(s, i)),
                        default=-1) + 1
        for nxt in succ.get((s, i), ()):
            indeg[nxt] -= 1
            if indeg[nxt] == 0:
                q.append(nxt)
    if n_done != n_ops:
        raise ValueError(
            f"cyclic op dependencies for schedule={schedule!r} "
            f"pp={pp} v={v} nm={nm} ({n_ops - n_done} ops unplaced)")

    L = int(lvl.max()) + 1
    ldir = np.full((pp, L), -1, np.int32)
    ldep_s = np.full((pp, L), -1, np.int32)
    ldep_l = np.full((pp, L), -1, np.int32)
    for s, order in enumerate(orders):
        for i, (d, _c, _m) in enumerate(order):
            lv = lvl[s, i]
            ldir[s, lv] = 0 if d == "F" else 1
            if dep_s[s, i] >= 0:
                ldep_s[s, lv] = dep_s[s, i]
                ldep_l[s, lv] = lvl[dep_s[s, i], dep_i[s, i]]
    for a in (ldir, ldep_s, ldep_l):
        a.setflags(write=False)
    return ldir, ldep_s, ldep_l


def _shape_key(p: StepProgram) -> Tuple[str, int, int, int]:
    return (p.schedule, p.n_stages, p.v, p.n_micro)


def _stack_tables(shape_keys: Sequence[Tuple], key_rows: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Gather per-record tables (K, S, L), padded to the batch maxima
    with -1 sentinels; table construction is paid once per shape
    (memoized), the per-record cost is one fancy-index gather.
    ``shape_keys`` lists the batch's unique shape keys and ``key_rows``
    maps each record to its index in that list."""
    tabs = [_shape_tables(*key) for key in shape_keys]
    S = max(t[0].shape[0] for t in tabs)
    L = max(t[0].shape[1] for t in tabs)
    U = len(tabs)
    stacks = [np.full((U, S, L), -1, np.int32) for _ in range(3)]
    for u, tab in enumerate(tabs):
        for a, src in zip(stacks, tab):
            a[u, :src.shape[0], :src.shape[1]] = src
    return tuple(a[key_rows] for a in stacks)


# ---------------------------------------------------------------------------
# The wave recurrence — numpy level loop
# ---------------------------------------------------------------------------
def _wavefront_numpy(ldir: np.ndarray, ldep_s: np.ndarray,
                     ldep_l: np.ndarray, tau_f: np.ndarray,
                     tau_b: np.ndarray) -> np.ndarray:
    """(K,) body makespans from (K, S, L) tables."""
    K, S, L = ldir.shape
    hist = np.zeros((K, S, L))          # end time of the op at (s, lv)
    dev_end = np.zeros((K, S))          # running end per device
    kk = np.arange(K)[:, None]
    tf = tau_f[:, None]
    tb = tau_b[:, None]
    for lv in range(L):
        d = ldir[:, :, lv]                          # (K, S)
        act = d >= 0
        ds = ldep_s[:, :, lv]
        has = ds >= 0
        dep = np.where(
            has,
            hist[kk, np.where(has, ds, 0),
                 np.where(has, ldep_l[:, :, lv], 0)],
            0.0)
        tau = np.where(d == 0, tf, tb)
        val = np.maximum(dev_end, dep) + tau
        hist[:, :, lv] = np.where(act, val, 0.0)
        dev_end = np.where(act, val, dev_end)
    return dev_end.max(axis=1)


def _wavefront_numpy_chunked(shape_keys: Sequence[Tuple],
                             key_rows: np.ndarray, tau_f: np.ndarray,
                             tau_b: np.ndarray) -> np.ndarray:
    """(K,) body makespans, gathering tables and running the level loop
    in K-chunks bounded by ``NUMPY_CHUNK_BUDGET_BYTES`` of scratch."""
    K = key_rows.shape[0]
    tabs = [_shape_tables(*key) for key in shape_keys]
    S = max(t[0].shape[0] for t in tabs)
    L = max(t[0].shape[1] for t in tabs)
    per_rec = 20 * S * L              # hist float64 + 3 int32 tables
    kc = max(NUMPY_CHUNK_BUDGET_BYTES // max(per_rec, 1), 16)
    if kc >= K:
        return _wavefront_numpy(*_stack_tables(shape_keys, key_rows),
                                tau_f, tau_b)
    out = np.empty(K)
    for lo in range(0, K, kc):
        sl = slice(lo, min(lo + kc, K))
        out[sl] = _wavefront_numpy(
            *_stack_tables(shape_keys, key_rows[sl]), tau_f[sl], tau_b[sl])
    return out


# ---------------------------------------------------------------------------
# The wave recurrence — jax, unrolled at trace time per shape key
# ---------------------------------------------------------------------------
# row order of the per-record input matrix handed to both backends
# (spans + per-program scalars, gathered once per unique program)
_ROW_KEYS = ("tau_f", "tau_b", "t_dp", "credit", "nmv", "analytic")
# row order of the stacked result matrix
_RES_KEYS = ("step_time", "makespan_body", "bubble", "dp_exposed", "err")


@functools.lru_cache(maxsize=512)
def _jax_shape_fn(schedule: str, pp: int, v: int, nm: int):
    """jit(rows (6, K) -> results (5, K)) for ONE shape key.

    The level tables are compile-time constants here, so the trace
    emits the recurrence as straight-line SSA over (K,) vectors: one
    ``maximum`` + ``add`` per op, dependencies resolved by NAME at
    trace time (no gathers, no carried history array, no loop; a
    traced ``fori_loop`` over levels measures ~15x slower on CPU).
    The bubble/DP epilogue is fused into the same trace.  The jit
    cache then keys only on the (bucketed) K — a new trace happens per
    (shape key, K-bucket), counted by ``_JAX_TRACES``."""
    import jax
    import jax.numpy as jnp

    ldir, ldep_s, ldep_l = _shape_tables(schedule, pp, v, nm)
    S, L = ldir.shape
    # plain int lists: the unroll below must not touch numpy at trace
    # time (jax-hygiene: no np.* inside a jit entry)
    ldir_t = [[int(x) for x in row] for row in ldir]
    ldep_s_t = [[int(x) for x in row] for row in ldep_s]
    ldep_l_t = [[int(x) for x in row] for row in ldep_l]

    def batch_fn(rows):
        # runs at TRACE time only — both side effects count retraces
        _JAX_TRACES["count"] += 1
        metrics.inc("batch_replay.jax_retraces")
        tau_f, tau_b, t_dp, credit, nmv, analytic = rows
        hist: Dict[Tuple[int, int], object] = {}
        dev_end: List[object] = [None] * S
        for lv in range(L):
            for s in range(S):
                d = ldir_t[s][lv]
                if d < 0:
                    continue
                tau = tau_f if d == 0 else tau_b
                # static table lookup, decided at trace time
                dep = hist[(ldep_s_t[s][lv], ldep_l_t[s][lv])] \
                    if ldep_s_t[s][lv] >= 0 else None  # chiplint: ignore[jax-hygiene]
                prev = dev_end[s]
                if prev is None and dep is None:
                    val = tau
                elif dep is None:
                    val = prev + tau
                elif prev is None:
                    val = dep + tau
                else:
                    val = jnp.maximum(prev, dep) + tau
                hist[(s, lv)] = val
                dev_end[s] = val
        body_end = dev_end[0]
        for s in range(1, S):
            # skip never-scheduled stages, known at trace time
            if dev_end[s] is not None:  # chiplint: ignore[jax-hygiene]
                body_end = jnp.maximum(body_end, dev_end[s])
        # epilogue: same expressions as the numpy path in replay_batch
        busy = nmv * (tau_f + tau_b)
        bubble = jnp.where(busy > 0, body_end / busy - 1.0, 0.0)
        dp_exposed = jnp.maximum(t_dp - credit, 0.0)
        dp_exposed = jnp.where(t_dp > 0, dp_exposed, 0.0)
        step_time = body_end + dp_exposed
        err = (step_time - analytic) / analytic
        return jnp.stack((step_time, body_end, bubble, dp_exposed, err))

    return jax.jit(batch_fn)


def _pad_edge(a: np.ndarray, nb: int) -> np.ndarray:
    """Edge-pad the trailing axis to the bucket: padded rows replicate
    the last real record, so the tail traces the same recurrence."""
    n = a.shape[-1]
    if nb == n:
        return a
    out = np.empty(a.shape[:-1] + (nb,))
    out[..., :n] = a
    out[..., n:] = a[..., n - 1:n]
    return out


def _replay_jax(shape_keys: Sequence[Tuple], key_rows: np.ndarray,
                rows: np.ndarray) -> np.ndarray:
    """(5, K) results from (6, K) inputs.  Group records by shape key
    (``key_rows`` maps row -> index into ``shape_keys``), one jit call
    per group, rows edge-padded to the next power-of-two bucket,
    scatter back."""
    import jax
    K = rows.shape[1]
    n_keys = len(shape_keys)
    metrics.inc("batch_replay.jax_calls", n_keys)
    with jax.enable_x64(True):
        if n_keys == 1:                 # fast path: no gather/scatter
            nb = _bucket(K)
            fn = _jax_shape_fn(*shape_keys[0])
            metrics.inc("batch_replay.jax_pad_rows", nb - K)
            return np.asarray(fn(_pad_edge(rows, nb)))[:, :K]
        out = np.empty((len(_RES_KEYS), K))
        for ki in range(n_keys):
            idx = np.nonzero(key_rows == ki)[0]
            n = idx.shape[0]
            nb = _bucket(n)
            fn = _jax_shape_fn(*shape_keys[ki])
            metrics.inc("batch_replay.jax_pad_rows", nb - n)
            out[:, idx] = np.asarray(fn(_pad_edge(rows[:, idx], nb)))[:, :n]
    return out


# ---------------------------------------------------------------------------
# replay_rows / replay_batch
# ---------------------------------------------------------------------------
def replay_rows(shape_keys: Sequence[Tuple], key_rows: np.ndarray,
                rows: np.ndarray, backend: str = "auto"
                ) -> Dict[str, np.ndarray]:
    """Replay K pre-compiled record rows: ``rows`` is the (6, K)
    ``_ROW_KEYS`` matrix, ``shape_keys`` the batch's unique
    (schedule, pp, v, n_micro) keys and ``key_rows`` the per-record
    index into it.  This is the shared wavefront entry: ``replay_batch``
    extracts rows from ``StepProgram``s, ``events.compile_batch`` builds
    them vectorized without any programs.  Returns the SoA result dict
    (see ``replay_batch``)."""
    K = rows.shape[1]
    if K == 0:
        out = {k: np.zeros(0) for k in
               ("step_time", "makespan_body", "bubble", "dp_exposed",
                "analytic_step_time", "err")}
        out["scalar_fallback"] = np.zeros(0, bool)
        return out
    metrics.inc("batch_replay.records", K)
    backend = resolve_backend(backend, K)

    if backend == "jax":
        res = _replay_jax(shape_keys, key_rows, rows)
        out = dict(zip(_RES_KEYS, res))
        out["analytic_step_time"] = rows[5]
        out["scalar_fallback"] = np.zeros(K, bool)
        return out

    tau_f, tau_b, t_dp, credit, nmv, analytic = rows
    body_end = _wavefront_numpy_chunked(shape_keys, key_rows, tau_f, tau_b)

    busy = nmv * (tau_f + tau_b)
    with np.errstate(invalid="ignore", divide="ignore"):
        bubble = np.where(busy > 0, body_end / busy - 1.0, 0.0)
        dp_exposed = np.maximum(t_dp - credit, 0.0)
        dp_exposed = np.where(t_dp > 0, dp_exposed, 0.0)
        step_time = body_end + dp_exposed
        err = (step_time - analytic) / analytic
    return {"step_time": step_time, "makespan_body": body_end,
            "bubble": bubble, "dp_exposed": dp_exposed,
            "analytic_step_time": analytic, "err": err,
            "scalar_fallback": np.zeros(K, bool)}


def replay_batch(programs: Sequence[StepProgram],
                 backend: str = "auto") -> Dict[str, np.ndarray]:
    """Replay K programs; returns SoA arrays over the batch:
    ``step_time``, ``makespan_body``, ``bubble``, ``dp_exposed``,
    ``analytic_step_time``, ``err``, plus a ``scalar_fallback`` bool
    mask kept for schema stability — always ``False`` now that every
    schedule (gpipe / 1f1b / interleaved) runs through the vectorized
    wavefront.  ``backend`` selects the recurrence implementation
    (``numpy`` | ``jax`` | ``auto``, see module docstring)."""
    K = len(programs)
    if K == 0:
        return replay_rows((), np.zeros(0, np.int64), np.zeros((6, 0)),
                           backend=backend)

    # Dedupe by object identity at C speed: bench batches and outer
    # rounds replay few unique programs many times, so all per-record
    # Python (span walks, attribute reads, shape keying) is paid once
    # per UNIQUE program.  Held references keep ids unique.
    ids = np.fromiter(map(id, programs), np.int64, count=K)
    _, first, inv = np.unique(ids, return_index=True, return_inverse=True)
    uprogs = [programs[int(i)] for i in first]
    urows = np.array([p.spans() + (p.n_micro * p.v,
                                   p.analytic.step_time if p.analytic
                                   else np.nan)
                      for p in uprogs])                 # (U, 6)
    key_of: Dict[Tuple, int] = {}
    ukey_idx = np.empty(len(uprogs), np.int64)
    for u, p in enumerate(uprogs):
        ukey_idx[u] = key_of.setdefault(_shape_key(p), len(key_of))
    shape_keys = list(key_of)
    key_rows = ukey_idx[inv]                            # (K,)
    rows = np.ascontiguousarray(urows[inv].T)           # (6, K)
    return replay_rows(shape_keys, key_rows, rows, backend=backend)

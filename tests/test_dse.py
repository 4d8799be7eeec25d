"""Tests for the batched DSE engine (repro.dse).

The contract under test: ``batched_simulate`` must reproduce the scalar
oracle ``core.simulator.simulate`` element-wise — same feasibility mask,
step times within 1e-9 relative — over >=1000 sampled design points,
plus Pareto / allocation / driver invariants.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.mcm import mcm_from_compute
from repro.core.network import allocate_links
from repro.core.simulator import simulate
from repro.core.traffic import PARALLELISMS
from repro.core.workload import Workload, paper_workload
from repro.dse.batched_sim import (MCMBatch, allocate_links_batch,
                                   batched_simulate)
from repro.dse.pareto import (crowding_distance, nondominated_sort,
                              pareto_mask)
from repro.dse.search import (BatchedEvaluator, search_exhaustive,
                              search_nsga2, search_prf_ucb, search_random,
                              sweep_design_space)
from repro.dse.space import (DesignSpace, P_IDX, StrategyBatch,
                             enumerate_strategy_batch)

W = paper_workload(global_batch=512)
TINY = Workload(model=get_config("tinyllama_1_1b"), seq_len=4096,
                global_batch=256)


def _assert_parity(w, batch, mcm, fabric, reuse, hw=None):
    res = batched_simulate(w, batch, mcm, fabric=fabric, reuse=reuse, hw=hw)
    n_checked = 0
    for i, s in enumerate(batch.to_strategies()):
        r = simulate(w, s, mcm, fabric=fabric, topo=None, reuse=reuse,
                     hw=hw)
        assert r.feasible == bool(res.feasible[i]), (s, r.reason)
        if r.feasible:
            assert res.step_time[i] == pytest.approx(r.step_time, rel=1e-9)
            assert res.throughput[i] == pytest.approx(r.throughput,
                                                      rel=1e-9)
        n_checked += 1
    return n_checked


# ---------------------------------------------------------------------------
# Enumeration
# ---------------------------------------------------------------------------
def test_enumeration_matches_scalar():
    from repro.core.optimizer import enumerate_strategies
    for w, c in ((W, 4e6), (TINY, 1e6)):
        mcm = mcm_from_compute(c, dies_per_mcm=16, m=6)
        scal = {(s.tp, s.dp, s.pp, s.cp, s.ep, s.n_micro)
                for s in enumerate_strategies(w, mcm)}
        batch = enumerate_strategy_batch(w, mcm)
        soa = set(batch.keys())
        assert soa == scal and len(batch) == len(scal)


# ---------------------------------------------------------------------------
# Element-wise parity vs the scalar oracle (>= 1000 points total)
# ---------------------------------------------------------------------------
def test_parity_paper_workload_all_fabrics():
    mcm = mcm_from_compute(4e6, dies_per_mcm=16, m=6)
    batch = enumerate_strategy_batch(W, mcm)
    n = 0
    for fabric in ("oi", "ib", "nvlink"):
        n += _assert_parity(W, batch, mcm, fabric, reuse=True)
    n += _assert_parity(W, batch, mcm, "oi", reuse=False)
    assert n >= 1000          # the acceptance floor, on this test alone


def test_parity_includes_infeasible_and_invalid_points():
    rng = np.random.default_rng(3)
    mcm = mcm_from_compute(1e6, dies_per_mcm=16, m=2)   # tight HBM
    vals = np.array([1, 2, 4, 8, 16, 32, 64])
    batch = StrategyBatch(*(rng.choice(vals, 80) for _ in range(5)),
                          rng.choice([1, 2, 8, 32], 80))
    res = batched_simulate(W, batch, mcm)
    assert not res.feasible.all()            # invalid products / HBM
    _assert_parity(W, batch, mcm, "oi", reuse=True)


def test_parity_reuse_paper_mode_and_gemm_eff():
    mcm = mcm_from_compute(16e6, dies_per_mcm=16, m=8)
    hw_p = dataclasses.replace(mcm.hw, ocs_reuse_mode="paper")
    batch = enumerate_strategy_batch(W, mcm)
    sub = batch.take(np.arange(len(batch))[:: max(len(batch) // 80, 1)])
    _assert_parity(W, sub, mcm, "oi", reuse=True, hw=hw_p)
    hw_g = dataclasses.replace(mcm.hw, model_gemm_eff=True)
    _assert_parity(W, sub, mcm, "oi", reuse=True, hw=hw_g)


def test_parity_moe_free_and_fused_mcm_batch():
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi",),
                                     m=(2, 6), cpo_ratio=(0.3, 0.9))
    cells = list(space.batches())
    batch = StrategyBatch.concat([g for _, _, g in cells])
    local = np.concatenate([np.full(len(g), i, np.int64)
                            for i, (_, _, g) in enumerate(cells)])
    mcms = [m for m, _, _ in cells]
    res = batched_simulate(TINY, batch, MCMBatch.from_mcms(mcms, local),
                           fabric="oi", reuse=True, hw=mcms[0].hw)
    for i, s in enumerate(batch.to_strategies()):
        r = simulate(TINY, s, mcms[local[i]], fabric="oi", topo=None)
        assert r.feasible == bool(res.feasible[i])
        if r.feasible:
            assert res.step_time[i] == pytest.approx(r.step_time, rel=1e-9)


def test_jax_backend_matches_numpy():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    batch = enumerate_strategy_batch(W, mcm)
    rn = batched_simulate(W, batch, mcm, backend="numpy")
    rj = batched_simulate(W, batch, mcm, backend="jax")
    assert np.array_equal(rn.feasible, rj.feasible)
    ok = rn.feasible
    np.testing.assert_allclose(rj.step_time[ok], rn.step_time[ok],
                               rtol=1e-9)


# ---------------------------------------------------------------------------
# Link allocation
# ---------------------------------------------------------------------------
def test_allocate_links_batch_matches_scalar():
    rng = np.random.default_rng(7)
    B = 300
    vols = rng.uniform(1e6, 1e12, size=(B, 5))
    mask = rng.random((B, 5)) < 0.7
    vols = np.where(mask, vols, 0.0)
    pair_choices = [(-1, -1), (P_IDX["CP"], P_IDX["EP"]),
                    (P_IDX["CP"], P_IDX["DP"]), (P_IDX["EP"], P_IDX["DP"])]
    picks = rng.integers(len(pair_choices), size=B)
    pa = np.array([pair_choices[p][0] for p in picks])
    pb = np.array([pair_choices[p][1] for p in picks])
    # a pair only counts when both members carry inter traffic
    valid = (pa >= 0) & mask[np.arange(B), np.maximum(pa, 0)] \
        & mask[np.arange(B), np.maximum(pb, 0)]
    pa, pb = np.where(valid, pa, -1), np.where(valid, pb, -1)
    for L in (3, 17, 96):
        got = allocate_links_batch(vols, mask, L, pa, pb)
        for i in range(B):
            d = {p: vols[i, P_IDX[p]] for p in PARALLELISMS
                 if mask[i, P_IDX[p]]}
            rp = None
            if pa[i] >= 0:
                rp = (PARALLELISMS[pa[i]], PARALLELISMS[pb[i]])
            want = allocate_links(d, L, rp)
            for p, v in want.items():
                assert got[i, P_IDX[p]] == v, (i, L, d, rp, want)


def test_allocate_links_reuse_respects_budget():
    # the fixed trim: l_reuse + others (pair counted once) <= L
    vols = {"CP": 5e9, "EP": 9e9, "DP": 4e9, "PP": 1e3}
    for L in (3, 4, 5, 8, 64):
        alloc = allocate_links(vols, L, ("CP", "EP"))
        used = alloc["CP"] + alloc["DP"] + alloc["PP"]
        assert used <= L or max(alloc.values()) <= 1
        assert alloc["CP"] == alloc["EP"]


# ---------------------------------------------------------------------------
# Pareto invariants
# ---------------------------------------------------------------------------
def test_pareto_mask_no_dominated_survivor():
    rng = np.random.default_rng(0)
    obj = rng.normal(size=(400, 3))
    obj[50:60] = obj[40:50]                  # duplicates must survive
    maximize = [True, False, True]
    keep = pareto_mask(obj, maximize)
    sign = np.where(maximize, 1.0, -1.0)
    M = obj * sign
    for i in np.nonzero(keep)[0]:
        dom = (M >= M[i]).all(1) & (M > M[i]).any(1)
        assert not dom.any()
    # and every removed point IS dominated by someone
    for i in np.nonzero(~keep)[0]:
        dom = (M >= M[i]).all(1) & (M > M[i]).any(1)
        assert dom.any()


def test_nondominated_sort_fronts_are_clean():
    rng = np.random.default_rng(1)
    obj = rng.normal(size=(200, 2))
    maximize = [True, True]
    ranks = nondominated_sort(obj, maximize)
    assert (ranks[pareto_mask(obj, maximize)] == 0).all()
    for r in range(int(ranks.max()) + 1):
        sel = ranks >= r
        front = pareto_mask(obj[sel], maximize)
        assert (ranks[np.nonzero(sel)[0][front]] == r).all()
    d = crowding_distance(obj[ranks == 0], maximize)
    assert np.isinf(d).sum() >= 2            # boundary points


def test_sweep_pareto_and_best():
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi", "ib"),
                                     m=(2, 6, 8), cpo_ratio=(0.6,))
    sweep = sweep_design_space(space)
    assert len(sweep) > 500
    pi = sweep.pareto_indices()
    assert len(pi) > 0
    best = sweep.best
    t, c, p = (sweep.metrics["throughput"], sweep.metrics["cost"],
               sweep.metrics["power"])
    feas = np.nonzero(sweep.metrics["feasible"])[0]
    for i in pi:
        dom = (t[feas] >= t[i]) & (c[feas] <= c[i]) & (p[feas] <= p[i]) \
            & ((t[feas] > t[i]) | (c[feas] < c[i]) | (p[feas] < p[i]))
        assert not dom.any()
    assert best in pi                        # max-throughput is on the front


# ---------------------------------------------------------------------------
# Drivers + cache
# ---------------------------------------------------------------------------
def test_drivers_and_cache():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    full = search_exhaustive(BatchedEvaluator(W, mcm))
    t_best = full.metrics["throughput"].max()
    assert full.metrics["feasible"].any()

    r = search_random(BatchedEvaluator(W, mcm), budget=60, seed=0)
    assert r.n_sim <= 60
    p = search_prf_ucb(BatchedEvaluator(W, mcm), budget=60, seed=0)
    assert p.n_sim <= 60
    assert p.metrics["throughput"].max() <= t_best + 1e-9
    g = search_nsga2(BatchedEvaluator(W, mcm), pop_size=16, generations=4,
                     seed=0)
    assert g.metrics["throughput"].max() <= t_best + 1e-9
    assert (g.batch.n_devices == mcm.n_devices).all()   # repair keeps grid

    ev = BatchedEvaluator(W, mcm)
    search_exhaustive(ev)
    n = ev.n_sim
    again = search_exhaustive(ev)
    assert ev.n_sim == n and ev.n_hits >= len(again.batch)


# ---------------------------------------------------------------------------
# Vectorized evaluation cache
# ---------------------------------------------------------------------------
def test_evaluator_cache_vectorized_hits_and_values():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    ev = BatchedEvaluator(W, mcm)
    grid = enumerate_strategy_batch(W, mcm)
    half = grid.take(np.arange(len(grid) // 2))
    m1 = ev.evaluate(half)
    assert ev.n_sim == len(half) and ev.n_hits == 0
    m2 = ev.evaluate(grid)                    # first half must be hits
    assert ev.n_hits == len(half)
    assert ev.n_sim == len(grid)
    for k in m1:
        np.testing.assert_array_equal(m2[k][: len(half)], m1[k])
    # duplicate rows inside one batch resolve consistently
    dup = grid.take(np.array([0, 0, 1, 1, 0]))
    m3 = ev.evaluate(dup)
    assert m3["step_time"][0] == m3["step_time"][1] == m3["step_time"][4]


def test_evaluator_cache_falls_back_on_unpackable_degrees():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    ev = BatchedEvaluator(W, mcm)
    huge = StrategyBatch(*(np.full(4, 1 << 11, np.int64)
                           for _ in range(6)))
    m1 = ev.evaluate(huge)                    # 6 x 12 bits > 64 -> dict
    assert ev._fallback is not None
    assert not m1["feasible"].any()
    m2 = ev.evaluate(huge)
    assert ev.n_hits >= len(huge)             # still caches correctly
    np.testing.assert_array_equal(m1["step_time"], m2["step_time"])


def test_evaluator_cache_repacks_when_widths_grow():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    ev = BatchedEvaluator(W, mcm)
    grid = enumerate_strategy_batch(W, mcm)
    small = grid.take(np.arange(8))
    ev.evaluate(small)
    wide = StrategyBatch(np.array([4096]), np.array([1]), np.array([1]),
                         np.array([1]), np.array([1]), np.array([1]))
    ev.evaluate(wide)                         # forces width growth+repack
    n = ev.n_sim
    m = ev.evaluate(small)                    # old keys still hit
    assert ev.n_sim == n
    assert len(m["step_time"]) == len(small)


# ---------------------------------------------------------------------------
# Fused multi-cell driving == per-cell driving
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("driver,kw", [
    ("random", {"budget": 24}),
    ("prf", {"budget": 24}),
    ("nsga2", {"pop_size": 10, "generations": 2}),
])
def test_sweep_fused_driver_matches_per_cell(driver, kw):
    from repro.dse.search import DRIVERS
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi", "ib"),
                                     m=(2, 6), cpo_ratio=(0.6,))
    sweep = sweep_design_space(space, driver=driver, seed=3, **kw)
    run = DRIVERS[driver]
    pos = {id(m): i for i, m in enumerate(space.mcms)}
    tp, thpt, cost, mi, fb = [], [], [], [], []
    for ci, (mcm, fabric, grid) in enumerate(space.batches()):
        ev = BatchedEvaluator(space.workload, mcm, fabric, space.reuse)
        res = run(ev, grid=grid, seed=3 + ci, **kw)
        tp.append(res.batch.tp)
        thpt.append(res.metrics["throughput"])
        cost.append(res.metrics["cost"])
        mi.append(np.full(len(res.batch), pos[id(mcm)]))
        fb.append(np.full(len(res.batch), fabric))
    assert np.array_equal(sweep.batch.tp, np.concatenate(tp))
    assert np.array_equal(sweep.metrics["throughput"],
                          np.concatenate(thpt))
    assert np.array_equal(sweep.metrics["cost"], np.concatenate(cost))
    assert np.array_equal(sweep.mcm_idx, np.concatenate(mi))
    assert np.array_equal(sweep.fabric, np.concatenate(fb))


def test_fused_paths_respect_per_mcm_hw():
    """A hand-built DesignSpace may mix HW configs across MCM variants;
    fused sweeps and batched refinement must simulate each cell with
    ITS hw, not the first cell's."""
    import dataclasses as dc
    from repro.dse.search import refine_top_points
    m1 = mcm_from_compute(1e6, dies_per_mcm=16, m=6)
    hw2 = dc.replace(m1.hw, mfu_ceiling=m1.hw.mfu_ceiling / 2)
    m2 = dc.replace(mcm_from_compute(1e6, dies_per_mcm=16, m=2), hw=hw2)
    space = DesignSpace(workload=TINY, mcms=(m1, m2), fabrics=("oi",))
    for driver, kw in (("exhaustive", {}), ("random", {"budget": 16})):
        sweep = sweep_design_space(space, driver=driver, **kw)
        for i in (0, len(sweep) - 1):
            s = sweep.batch.take(np.array([i])).to_strategies()[0]
            mcm = space.mcms[int(sweep.mcm_idx[i])]
            r = simulate(TINY, s, mcm, fabric="oi", topo=None,
                         hw=mcm.hw)
            assert bool(sweep.metrics["feasible"][i]) == r.feasible
            if r.feasible:
                assert sweep.metrics["step_time"][i] == pytest.approx(
                    r.step_time, rel=1e-9)
    sweep = sweep_design_space(space)
    got = refine_top_points(sweep, top_k=12)
    want = refine_top_points(sweep, top_k=12, method="scalar")
    assert [p.strategy for p in got] == [p.strategy for p in want]
    for pg, pw in zip(got, want):
        assert pg.throughput == pytest.approx(pw.throughput, rel=1e-9)


# ---------------------------------------------------------------------------
# Batched refinement == scalar oracle (dense + MoE presets)
# ---------------------------------------------------------------------------
def _assert_refine_parity(space, top_k):
    from repro.dse.search import refine_top_points
    sweep = sweep_design_space(space)
    batched = refine_top_points(sweep, top_k=top_k)
    scalar = refine_top_points(sweep, top_k=top_k, method="scalar")
    assert len(batched) == len(scalar) > 0
    for pb, ps in zip(batched, scalar):
        assert pb.strategy == ps.strategy          # identical ranking
        assert pb.mcm == ps.mcm and pb.fabric == ps.fabric
        assert pb.throughput == pytest.approx(ps.throughput, rel=1e-9)
        assert pb.cost == pytest.approx(ps.cost, rel=1e-9)
        assert pb.sim.step_time == pytest.approx(ps.sim.step_time,
                                                 rel=1e-9)
        assert pb.sim.mfu == pytest.approx(ps.sim.mfu, rel=1e-9)
        if ps.topo is None:
            assert pb.topo is None
        else:
            assert pb.topo.dims == ps.topo.dims
            assert pb.topo.mapping == ps.topo.mapping
            assert dict(pb.topo.link_alloc) == dict(ps.topo.link_alloc)
            assert pb.topo.reuse_pair == ps.topo.reuse_pair
        assert pb.sim.bottleneck == ps.sim.bottleneck
        assert set(pb.sim.breakdown) == set(ps.sim.breakdown)
        for k, v in ps.sim.logs.items():
            assert pb.sim.logs[k] == pytest.approx(v, rel=1e-9, abs=0.0), k
    return batched


def test_refine_batched_matches_scalar_dense():
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi", "ib"),
                                     m=(2, 6), cpo_ratio=(0.3, 0.9))
    _assert_refine_parity(space, top_k=24)


def test_refine_batched_matches_scalar_moe():
    space = DesignSpace.from_compute(W, 4e6, fabrics=("oi",),
                                     dies_per_mcm=(8, 16), m=(4, 6),
                                     cpo_ratio=(0.6,))
    pts = _assert_refine_parity(space, top_k=24)
    # refined OI points carry a derived physical topology
    assert any(p.topo is not None and p.topo.dims for p in pts)


def test_refine_board_power_matches_scalar_records():
    from repro.api import record_from_point
    from repro.dse.search import refine_top_points
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi",),
                                     m=(2, 6), cpo_ratio=(0.6,))
    sweep = sweep_design_space(space)
    recs_b = [record_from_point(p)
              for p in refine_top_points(sweep, top_k=8)]
    recs_s = [record_from_point(p)
              for p in refine_top_points(sweep, top_k=8,
                                         method="scalar")]
    for rb, rs in zip(recs_b, recs_s):
        for k in ("throughput", "cost", "power"):
            assert rb.metrics[k] == pytest.approx(rs.metrics[k],
                                                  rel=1e-9), k


def test_refine_rejects_unknown_method():
    from repro.dse.search import refine_top_points
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi",),
                                     m=(6,), cpo_ratio=(0.6,))
    sweep = sweep_design_space(space)
    with pytest.raises(ValueError, match="refine method"):
        refine_top_points(sweep, top_k=2, method="quantum")


# ---------------------------------------------------------------------------
# JAX backend: bucketed jit cache + auto resolution
# ---------------------------------------------------------------------------
def test_jax_backend_parity_all_fabrics_and_fused():
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    batch = enumerate_strategy_batch(W, mcm)
    for fabric in ("oi", "ib", "nvlink"):
        rn = batched_simulate(W, batch, mcm, fabric=fabric,
                              backend="numpy")
        rj = batched_simulate(W, batch, mcm, fabric=fabric,
                              backend="jax")
        assert np.array_equal(rn.feasible, rj.feasible)
        ok = rn.feasible
        np.testing.assert_allclose(rj.step_time[ok], rn.step_time[ok],
                                   rtol=1e-9)
        np.testing.assert_allclose(rj.power[ok], rn.power[ok], rtol=1e-9)
    # heterogeneous MCMBatch through the jax path
    space = DesignSpace.from_compute(TINY, 1e6, fabrics=("oi",),
                                     m=(2, 6), cpo_ratio=(0.3, 0.9))
    cells = list(space.batches())
    fused = StrategyBatch.concat([g for _, _, g in cells])
    local = np.concatenate([np.full(len(g), i, np.int64)
                            for i, (_, _, g) in enumerate(cells)])
    mb = MCMBatch.from_mcms([m for m, _, _ in cells], local)
    hw = cells[0][0].hw
    rn = batched_simulate(TINY, fused, mb, hw=hw, backend="numpy")
    rj = batched_simulate(TINY, fused, mb, hw=hw, backend="jax")
    assert np.array_equal(rn.feasible, rj.feasible)
    ok = rn.feasible
    np.testing.assert_allclose(rj.step_time[ok], rn.step_time[ok],
                               rtol=1e-9)
    # no-reuse path too
    rn = batched_simulate(W, batch, mcm, reuse=False, backend="numpy")
    rj = batched_simulate(W, batch, mcm, reuse=False, backend="jax")
    np.testing.assert_allclose(rj.step_time[rn.feasible],
                               rn.step_time[rn.feasible], rtol=1e-9)


def test_jax_bucketed_jit_does_not_retrace():
    from repro.dse import batched_sim as bs
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    batch = enumerate_strategy_batch(W, mcm)
    n0 = len(batch) // 2
    batched_simulate(W, batch.take(np.arange(n0)), mcm, backend="jax")
    before = bs._JAX_TRACES["count"]
    for n in range(n0, n0 + 8):       # same power-of-two bucket
        batched_simulate(W, batch.take(np.arange(n)), mcm,
                         backend="jax")
    assert bs._JAX_TRACES["count"] == before


def test_auto_backend_resolution():
    from repro.dse.batched_sim import JAX_AUTO_MIN_BATCH, resolve_backend
    assert resolve_backend("numpy", 10 ** 9) == "numpy"
    assert resolve_backend("jax", 1) == "jax"
    assert resolve_backend("auto", 4) == "numpy"
    assert resolve_backend("auto", JAX_AUTO_MIN_BATCH) == "jax"
    mcm = mcm_from_compute(1e6, dies_per_mcm=16, m=6)
    batch = enumerate_strategy_batch(TINY, mcm)
    ra = batched_simulate(TINY, batch, mcm, backend="auto")
    rn = batched_simulate(TINY, batch, mcm, backend="numpy")
    ok = rn.feasible
    np.testing.assert_allclose(ra.step_time[ok], rn.step_time[ok],
                               rtol=1e-9)


# ---------------------------------------------------------------------------
# pareto_mask: randomized brute-force cross-check
# ---------------------------------------------------------------------------
def _pareto_bruteforce(obj, maximize):
    """The O(N^2) definition: no non-NaN row is >= everywhere and >
    somewhere."""
    M = obj * np.where(maximize, 1.0, -1.0)
    ok = ~np.isnan(M).any(1)
    want = ok.copy()
    for j in np.nonzero(ok)[0]:
        want[j] = not ((M >= M[j]).all(1) & (M > M[j]).any(1) & ok).any()
    return want


def _pareto_case(kind, k, rng):
    """Objective matrices shaped like what the sweep feeds ``pareto_mask``
    and like its edge cases."""
    n = int(rng.integers(2, 2000))
    if kind == "normal":
        obj = rng.normal(size=(n, k))
    elif kind == "few_levels":          # cost-like: ~48 distinct values
        obj = rng.normal(size=(n, k))
        obj[:, -1] = rng.integers(48, size=n) * 0.25
        obj[:, 0] = rng.integers(48, size=n) * 1e3
    elif kind == "int_grid":            # most rows tie somewhere
        obj = rng.integers(4, size=(n, k)).astype(float)
    elif kind == "dup_rows":
        obj = rng.normal(size=(n, k))
        obj[rng.integers(n, size=n // 2)] = obj[rng.integers(n, size=n // 2)]
    elif kind == "obj0_ties":
        obj = rng.normal(size=(n, k))
        obj[:, 0] = np.round(obj[:, 0], 1)
    elif kind == "inf_signed_zero":
        obj = rng.choice([-np.inf, -1.0, -0.0, 0.0, 1.0, np.inf], size=(n, k))
        obj[rng.integers(n, size=n // 20)] = np.nan
    elif kind == "all_nan":
        obj = np.full((n, k), np.nan)
    else:                               # single_row
        obj = rng.normal(size=(1, k))
    if kind not in ("all_nan", "single_row"):
        obj[rng.integers(n, size=max(1, n // 100)), rng.integers(k)] = np.nan
    return obj


_PARETO_KINDS = ("normal", "few_levels", "int_grid", "dup_rows",
                 "obj0_ties", "inf_signed_zero", "all_nan", "single_row")


@pytest.mark.parametrize("k", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("kind", _PARETO_KINDS)
def test_pareto_mask_matches_bruteforce_randomized(kind, k):
    rng = np.random.default_rng([11, _PARETO_KINDS.index(kind), k])
    obj = _pareto_case(kind, k, rng)
    maximize = [bool(b) for b in rng.integers(2, size=k)]
    want = _pareto_bruteforce(obj, maximize)
    for chunk in (1, 7, 64, 512):
        assert np.array_equal(pareto_mask(obj, maximize, chunk=chunk), want)


@pytest.mark.parametrize("k", [2, 3, 4])
def test_pareto_mask_counts_rows(k):
    from repro.obs import metrics as obs_metrics
    obj = np.random.default_rng(3).integers(5, size=(300, k)).astype(float)
    obj[0] = np.nan
    n_dup = 299 - len(np.unique(obj[1:], axis=0))
    with obs_metrics.scope() as m:
        pareto_mask(obj, [True] * k)
    c = m.counters
    assert c["pareto.rows"] == 299
    assert c["pareto.dup_rows"] == n_dup > 0
    assert c.get("pareto.staircase_rows", 0) == (299 if k <= 3 else 0)


def test_inner_search_uses_batched_scan():
    from repro.core.optimizer import inner_search
    mcm = mcm_from_compute(2e6, dies_per_mcm=16, m=6)
    best, pts = inner_search(W, mcm, budget=16)
    assert best is not None and len(pts) <= 16
    # the refined best must be the throughput argmax of its pool
    assert best.throughput == max(p.throughput for p in pts)
    # and must sit at the top of the batched ranking of the full grid
    ev = BatchedEvaluator(W, mcm)
    full = search_exhaustive(ev)
    assert best.throughput >= 0.95 * full.metrics["throughput"].max()

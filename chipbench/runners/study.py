"""Study cells: one cluster architect running ``Study.run()`` back to
back, each study at its own compute budget C.

The configuration file holds the deployment (model sizes, sequence,
global batch, hardware overrides, the scenario fields the program reads,
``backend: auto`` among them); the traffic file holds what each study
asks (the grid, the driver and its knobs, the range of C).  C runs over
a low-discrepancy sequence in log C, rotated by the seed: every seed
asks the same spread of budgets in another order, and no two studies of
a run share a budget.

Correctness: once the window has closed, a sample of the studies it
completed (drawn from the seed, with the study that scanned the most
rows always in it) is answered again by the plain reference in
``chipbench.reference.study`` and compared record by record.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Tuple

import numpy as np

from chipbench import harness
from chipbench.reference import study as ref
from chipbench.reference.chiplight.optimizer import evaluate_point

# Relative gap under which two throughputs (or objective values) count
# as tied: rankings, cut-offs and dominance inside it are not judged.
TIE_RTOL = 1e-9
COST = [m for m, _ in ref.OBJECTIVES].index("cost")


def budget_sequence(lo: float, hi: float, n: int, offset: float
                    ) -> np.ndarray:
    """``n`` budgets log-spread over [lo, hi]: the base-2 van der Corput
    sequence, rotated by ``offset`` in [0, 1)."""
    u = np.empty(n)
    for i in range(n):
        k, f, x = i + 1, 0.5, 0.0
        while k:
            x += f * (k & 1)
            k >>= 1
            f *= 0.5
        u[i] = (x + offset) % 1.0
    return lo * (hi / lo) ** u


def ladder(lo: float, hi: float, n: int) -> np.ndarray:
    """Warm-up budgets: ``n`` log-spaced points, both ends included."""
    return lo * (hi / lo) ** np.linspace(0.0, 1.0, n)


def scenario_dict(config: Dict, traffic: Dict) -> Dict:
    """The program's scenario fields for one study (C is set per call)."""
    dep = config["deployment"]
    grid = traffic["grid"]
    return {"model": config["model_arch"], "seq_len": dep["seq_len"],
            "global_batch": dep["global_batch"], "hw": dict(dep["hw"]),
            "reuse": dep["reuse"], "backend": dep["backend"],
            "objectives": list(dep["objectives"]),
            "dies_per_mcm": grid["dies_per_mcm"], "m": grid["m"],
            "cpo_ratio": grid["cpo_ratio"], "fabrics": grid["fabrics"],
            "driver": traffic["driver"], "driver_kw": traffic["driver_kw"],
            "schedule": traffic["schedule"], "keep_top": traffic["keep_top"],
            "refine_top": traffic["refine_top"],
            "validate_top": traffic["validate_top"],
            "name": config["name"], "total_tflops": traffic["C"]["low"]}


class Cell:
    def __init__(self, name: str, config: Dict, traffic: Dict, seed: int,
                 devices):
        from repro.api import Scenario
        self.name, self.config, self.traffic = name, config, traffic
        self.seed = seed
        self.base = Scenario.from_dict(scenario_dict(config, traffic))
        c = traffic["C"]
        self.lo, self.hi = float(c["low"]), float(c["high"])
        self.budgets = budget_sequence(
            self.lo, self.hi, int(traffic["max_studies"]),
            float(harness.rng(seed, 1).random()))
        self.pick = harness.rng(seed, 2)
        self.kept: List[Tuple[float, object]] = []    # sampled studies
        self.longest: Tuple[int, float, object] = (-1, 0.0, None)
        self.times: List[float] = []

    # -- set-up --------------------------------------------------------
    def _study(self, C: float):
        from repro.api import Study
        return Study(self.base.replace(total_tflops=float(C))).run()

    def setup(self) -> None:
        """Every C on the ladder once: each row bucket and program the
        window can reach is compiled and cached here."""
        for C in ladder(self.lo, self.hi, int(self.traffic["warmup"])):
            self._study(C)

    # -- the window ----------------------------------------------------
    def _compiles(self) -> int:
        from repro.dse.batched_sim import jax_stats as scan_stats
        from repro.events.batch import jax_stats as replay_stats
        return scan_stats()["traces"] + replay_stats()["traces"]

    def window(self, seconds: float) -> Dict:
        import jax
        from repro.obs import metrics
        k = int(self.traffic["check_studies"]) - 1
        seen = [0]

        def unit() -> bool:
            i = seen[0]
            if i >= len(self.budgets):
                raise harness.BenchError(
                    f"the window outran max_studies={len(self.budgets)}")
            C = float(self.budgets[i])
            seen[0] += 1
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.study"):
                res = self._study(C)
            self.times.append(time.perf_counter() - t)
            rows = int(res.provenance["grid_evaluated"])
            if rows > self.longest[0]:
                self.longest = (rows, C, res)
            # reservoir sample of k studies, drawn from the seed
            if len(self.kept) < k:
                self.kept.append((C, res))
            else:
                j = int(self.pick.integers(0, i + 1))
                if j < k:
                    self.kept[j] = (C, res)
            return res.best is not None

        compiles0 = self._compiles()
        with metrics.scope() as ms:
            window_s, done, failed = harness.window_loop(unit, seconds)
        n = done + failed
        counters = dict(ms.snapshot()["counters"])
        counters["compiles"] = self._compiles() - compiles0
        times = sorted(self.times)
        p95 = float(np.quantile(times, 0.95, method="inverted_cdf"))
        return {"window_s": window_s, "units": n, "attempted": n,
                "failed": failed, "counters": counters,
                "e2e": {"study_s": window_s / n, "study_p95_s": p95}}

    def free(self) -> None:
        """The study keeps no device state between calls."""

    # -- correctness ---------------------------------------------------
    def samples(self) -> List[Tuple[float, object]]:
        out = list(self.kept)
        _, C, res = self.longest
        if res is not None and all(r is not res for _, r in out):
            out.append((C, res))
        return out

    def check(self) -> List[Tuple[str, float, float]]:
        limits = self.traffic["limits"]
        w, hw = ref.build_workload(self.config)
        agg = {"rel_err": 0.0, "rows_missing": 0, "rank_diff": 0,
               "frontier_diff": 0, "topo_diff": 0}
        for C, res in self.samples():
            r = ref.reference_study(
                w, hw, C, self.traffic["grid"], self.traffic["refine_top"],
                reuse=self.config["deployment"]["reuse"])
            got = compare(res, r, self.traffic)
            harness.say(f"check C={C!r}: " + " ".join(
                f"{k}={v!r}" for k, v in got.items()))
            agg["rel_err"] = max(agg["rel_err"], got["rel_err"])
            for key in ("rows_missing", "rank_diff", "frontier_diff",
                        "topo_diff"):
                agg[key] += got[key]
        return [(k, float(v), float(limits[k])) for k, v in agg.items()]


# ---------------------------------------------------------------------------
# The comparison
# ---------------------------------------------------------------------------
def _rel(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


class Ranking:
    """Rows best first by a score; ``clear(i, j)``: row i scores above
    row j beyond the tie band."""

    def __init__(self, rows, score):
        self.rows = [int(i) for i in rows]
        self.score = score

    def clear(self, i: int, j: int) -> bool:
        return bool(self.score[i] > self.score[j] * (1 + TIE_RTOL))

    def sure_top(self, k: int) -> List[int]:
        """Rows of the first ``k`` clearly above the first row left out."""
        if len(self.rows) <= k:
            return self.rows[:k]
        cut = self.rows[k]
        return [i for i in self.rows[:k] if self.clear(i, cut)]


def compare(res, r: Dict, traffic: Dict) -> Dict:
    """One study's records against the reference's answer.

    rel_err        largest relative gap of any record's throughput, step
                   time, MFU, cost or power;
    rows_missing   rows the reference ranks clearly in the top
                   ``keep_top``, clearly on the grid's frontier or
                   clearly among the ``refine_top`` refined, that the
                   study left out; records the reference does not know
                   or finds infeasible;
    rank_diff      kept rows out of throughput order, refined records
                   the reference ranks clearly outside the refined set;
    frontier_diff  records the study calls non-dominated that another
                   record clearly dominates, and records clearly
                   non-dominated that it left off its frontier;
    topo_diff      refined records whose derived topology differs.
    """
    keep_top, refine_top = traffic["keep_top"], traffic["refine_top"]
    g, w, hw, reuse = r["grid"], r["w"], r["hw"], r["reuse"]
    index = {k: i for i, k in enumerate(g["keys"])}
    out = {"rel_err": 0.0, "rows_missing": 0, "rank_diff": 0,
           "frontier_diff": 0, "topo_diff": 0}
    rank = Ranking(r["order"], g["throughput"])
    ref_refined = {g["keys"][i]: pt for i, pt in r["refined"]}

    rec_vals, batched_rows = [], []
    for rec in res.records:
        key = ref.record_key(rec.strategy, rec.mcm, rec.fabric)
        i = index.get(key)
        if i is None or not g["feasible"][i] or not rec.feasible:
            out["rows_missing"] += 1
            rec_vals.append(None)
            continue
        if rec.source == "refined":
            pt = ref_refined.get(key)
            if pt is None:
                # outside the reference's refined set: judged by rank
                s, mcm, fabric = g["rows"][i]
                pt = evaluate_point(w, s, mcm, fabric=fabric, reuse=reuse,
                                    hw=hw)
                edge = rank.rows[min(refine_top, len(rank.rows)) - 1]
                if rank.clear(edge, int(i)):
                    out["rank_diff"] += 1
            if pt is None:
                out["rows_missing"] += 1
                rec_vals.append(None)
                continue
            vals = ref.refined_metrics(pt)
            if rec.topo != ref.topo_dict(pt.topo):
                out["topo_diff"] += 1
        else:
            vals = {m: float(g[m][i]) for m in ref.METRICS}
            batched_rows.append(int(i))
        for m in ref.METRICS:
            out["rel_err"] = max(out["rel_err"],
                                 _rel(float(rec.metrics[m]), vals[m]))
        rec_vals.append(vals)

    # completeness: the kept rows, the grid's frontier, the refined set
    need = set(rank.sure_top(keep_top))
    front = r["front"]
    need |= set(front[~ref.near_dominated(r["obj"], front, TIE_RTOL)]
                .tolist())
    out["rows_missing"] += len(need - set(batched_rows))
    refined_keys = {ref.record_key(x.strategy, x.mcm, x.fabric)
                    for x in res.records if x.source == "refined"}
    for i in rank.sure_top(refine_top):
        if ref_refined.get(g["keys"][i]) is not None \
                and g["keys"][i] not in refined_keys:
            out["rows_missing"] += 1

    # the kept rows come best first
    head = batched_rows[:keep_top]
    out["rank_diff"] += sum(rank.clear(b, a) for a, b in zip(head, head[1:]))

    # the frontier over the returned records, judged on reference values
    ok = [j for j, v in enumerate(rec_vals) if v is not None]
    if ok:
        obj = ref.objective_matrix({m: np.array([rec_vals[j][m] for j in ok])
                                    for m, _ in ref.OBJECTIVES})
        pos = {j: n for n, j in enumerate(ok)}
        claimed = {pos[j] for j in res.pareto if j in pos}
        exact = ref.pareto(obj)
        sure = set(exact[~ref.near_dominated(obj, exact, TIE_RTOL)]
                   .tolist())
        out["frontier_diff"] += len(sure - claimed)
        out["frontier_diff"] += sum(_clearly_dominated(obj, n)
                                    for n in claimed)
    return out


def _clearly_dominated(obj: np.ndarray, n: int) -> bool:
    """Another row is better everywhere beyond the tie band, save that
    an equal cost counts as equal: cost is the same scalar sum on both
    sides, while throughput and power may differ in their last bits."""
    slack = TIE_RTOL * np.maximum(np.abs(obj[n]), 1e-300)
    better = obj >= obj[n] + slack
    ge = better.copy()
    ge[:, COST] |= obj[:, COST] == obj[n, COST]
    hit = ge.all(1) & better.any(1)
    hit[n] = False
    return bool(hit.any())

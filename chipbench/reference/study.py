"""Plain reference of one exhaustive ChipLight study, built on the scalar
oracle in ``chipbench.reference.chiplight`` (one Python call per design
point, no arrays, no device).

``reference_study`` works out what ``Study.run()`` answers for an
exhaustive scenario with one pipeline schedule and no event stage:
every row of the grid (feasibility, step time, throughput, MFU, cost,
power), the rows ranked by throughput, the grid's Pareto set and the
refined winners (derived topology, OCS-inclusive cost).  Rows come in
the order the study scans them: fabric by fabric, MCM variant by
variant, strategy by strategy.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench.reference.chiplight.cost import cluster_cost
from chipbench.reference.chiplight.hardware import DEFAULT_HW, HW
from chipbench.reference.chiplight.mcm import MCMArch, mcm_from_compute
from chipbench.reference.chiplight.modelcfg import (AttnConfig, ModelConfig,
                                                    MoEConfig)
from chipbench.reference.chiplight.optimizer import (enumerate_strategies,
                                                     evaluate_point)
from chipbench.reference.chiplight.simulator import simulate
from chipbench.reference.chiplight.workload import Workload

# board power model (W), as the study states it on every record
DIE_IDLE_W = 150.0
DIE_DYN_W = 550.0
HBM_W_PER_STACK = 30.0
OI_W_PER_LINK = 15.0
NIC_W_PER_DEV = 25.0

METRICS = ("throughput", "step_time", "mfu", "cost", "power")
# objective columns of the frontier: (metric, maximize)
OBJECTIVES = (("throughput", True), ("cost", False), ("power", False))

Key = Tuple


def build_model(block: Dict) -> ModelConfig:
    """A ``ModelConfig`` from a configuration file's ``model`` block."""
    b = dict(block)
    attn = AttnConfig(**b.pop("attn")) if b.get("attn") else None
    b.pop("attn", None)
    moe = MoEConfig(**b.pop("moe")) if b.get("moe") else None
    b.pop("moe", None)
    return ModelConfig(attn=attn, moe=moe, **b)


def build_workload(cfg: Dict) -> Tuple[Workload, HW]:
    """(workload, hardware) of a study configuration file."""
    dep = cfg["deployment"]
    w = Workload(model=build_model(cfg["model"]), seq_len=dep["seq_len"],
                 global_batch=dep["global_batch"])
    hw = dataclasses.replace(DEFAULT_HW, **dep.get("hw", {}))
    return w, hw


def board_power(mcm: MCMArch, fabric: str, util: float) -> float:
    n_dev = mcm.n_devices
    power = n_dev * (DIE_IDLE_W + DIE_DYN_W * util) \
        + n_dev * mcm.m * HBM_W_PER_STACK
    if fabric == "oi":
        return power + mcm.n_mcm * mcm.total_links * OI_W_PER_LINK
    return power + n_dev * NIC_W_PER_DEV


def mcm_grid(total_tflops: float, dies: Sequence[int], ms: Sequence[int],
             cpos: Sequence[float], hw: HW) -> List[MCMArch]:
    """Feasible MCM variants at cluster compute C, duplicates dropped."""
    out, seen = [], set()
    for d in dies:
        for mi in ms:
            for r in cpos:
                mcm = mcm_from_compute(total_tflops, d, mi, cpo_ratio=r,
                                       hw=hw)
                key = (mcm.n_mcm, mcm.x, mcm.y, mcm.m, round(r, 6))
                if key in seen:
                    continue
                seen.add(key)
                if mcm.feasible() and mcm.total_links > 0:
                    out.append(mcm)
    return out


def record_key(strategy: Dict, mcm: Dict, fabric: str) -> Key:
    """Hashable identity of a design point, from record-shaped dicts."""
    return (fabric, int(mcm["n_mcm"]), int(mcm["x"]), int(mcm["y"]),
            int(mcm["m"]), round(float(mcm["cpo_ratio"]), 6),
            int(strategy["TP"]), int(strategy["DP"]), int(strategy["PP"]),
            int(strategy["CP"]), int(strategy["EP"]),
            int(strategy["n_micro"]))


def _key(s, mcm: MCMArch, fabric: str) -> Key:
    return (fabric, mcm.n_mcm, mcm.x, mcm.y, mcm.m,
            round(mcm.cpo_ratio, 6), s.tp, s.dp, s.pp, s.cp, s.ep,
            s.n_micro)


def topo_dict(topo) -> Dict:
    if topo is None:
        return None
    return {"dims": [[d.n, d.r, d.k] for d in topo.dims],
            "mapping": [list(g) for g in topo.mapping],
            "link_alloc": dict(topo.link_alloc),
            "reuse_pair": list(topo.reuse_pair) if topo.reuse_pair else None,
            "ocs_count": int(topo.ocs_count())}


def evaluate_grid(w: Workload, hw: HW, total_tflops: float,
                  grid: Dict, reuse: bool = True) -> Dict:
    """Every row of the grid through the scalar simulator."""
    mcms = mcm_grid(total_tflops, grid["dies_per_mcm"], grid["m"],
                    grid["cpo_ratio"], hw)
    keys, rows = [], []
    cols = {k: [] for k in ("feasible", *METRICS)}
    for fabric in grid["fabrics"]:
        for mcm in mcms:
            base_cost = cluster_cost(mcm, None, fabric=fabric, hw=hw).total
            for s in enumerate_strategies(w, mcm):
                sim = simulate(w, s, mcm, fabric=fabric, reuse=reuse, hw=hw)
                keys.append(_key(s, mcm, fabric))
                rows.append((s, mcm, fabric))
                cols["feasible"].append(sim.feasible)
                cols["throughput"].append(sim.throughput)
                cols["step_time"].append(sim.step_time)
                cols["mfu"].append(sim.mfu)
                cols["cost"].append(base_cost)
                cols["power"].append(
                    board_power(mcm, fabric, sim.logs["compute_util"])
                    if sim.feasible else np.inf)
    out = {k: np.asarray(v, bool if k == "feasible" else np.float64)
           for k, v in cols.items()}
    out["keys"] = keys
    out["rows"] = rows
    return out


def objective_matrix(cols: Dict[str, np.ndarray]) -> np.ndarray:
    """(N, 3) objectives, all turned to 'larger is better'."""
    return np.stack([cols[m] if mx else -cols[m] for m, mx in OBJECTIVES], 1)


def pareto(obj: np.ndarray) -> np.ndarray:
    """Indices of the rows no other row weakly dominates (>= everywhere,
    > somewhere); equal rows keep each other.  Rows are taken best
    throughput first, each tested against the front found so far."""
    order = np.lexsort((-obj[:, 2], -obj[:, 1], -obj[:, 0]))
    front: List[int] = []
    for i in order:
        if front:
            f = obj[front]
            ge = (f >= obj[i]).all(1)
            gt = (f > obj[i]).any(1)
            if (ge & gt).any():
                continue
        front.append(int(i))
    return np.asarray(sorted(front), np.int64)


def near_dominated(obj: np.ndarray, cand: np.ndarray, tol: float
                   ) -> np.ndarray:
    """For each row in ``cand``: is there another row within ``tol``
    (relative) of dominating it?  Such rows sit on a near-tie and are not
    judged either way."""
    slack = tol * np.maximum(np.abs(obj), 1e-300)
    out = np.zeros(len(cand), bool)
    for j, i in enumerate(cand):
        ge = (obj >= obj[i] - slack[i]).all(1)
        ge[i] = False
        out[j] = bool(ge.any())
    return out


def reference_study(w: Workload, hw: HW, total_tflops: float, grid: Dict,
                    refine_top: int, reuse: bool = True) -> Dict:
    """The grid, the rows ranked by throughput, the grid's frontier and
    the refined points."""
    g = evaluate_grid(w, hw, total_tflops, grid, reuse=reuse)
    feas = np.nonzero(g["feasible"])[0]
    order = feas[np.argsort(-g["throughput"][feas], kind="stable")]
    obj = objective_matrix(g)
    front = feas[pareto(obj[feas])]
    refined = []
    for i in order[:refine_top]:
        s, mcm, fabric = g["rows"][i]
        pt = evaluate_point(w, s, mcm, fabric=fabric, reuse=reuse, hw=hw)
        refined.append((int(i), pt))
    return {"grid": g, "order": order, "front": front, "obj": obj,
            "refined": refined, "w": w, "hw": hw, "reuse": reuse}


def refined_metrics(pt) -> Dict:
    """The record metrics of one refined point."""
    return {"throughput": pt.sim.throughput, "step_time": pt.sim.step_time,
            "mfu": pt.sim.mfu, "cost": pt.cost,
            "power": board_power(pt.mcm, pt.fabric,
                                 pt.sim.logs.get("compute_util", 0.0))}

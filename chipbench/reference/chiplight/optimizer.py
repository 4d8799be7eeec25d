"""Scalar design-point oracle: strategy enumeration and the full
per-point treatment (traffic, link allocation, reuse, physical rails,
simulation, cost), one Python call per point."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

from chipbench.reference.chiplight.cost import cluster_cost
from chipbench.reference.chiplight.hardware import HW
from chipbench.reference.chiplight.mcm import MCMArch
from chipbench.reference.chiplight.network import OITopology, allocate_links, \
    derive_physical_cached
from chipbench.reference.chiplight.simulator import SimResult, map_intra, simulate
from chipbench.reference.chiplight.traffic import Strategy, traffic_volumes, reusable_pairs
from chipbench.reference.chiplight.workload import Workload


# ---------------------------------------------------------------------------
# Strategy enumeration
# ---------------------------------------------------------------------------
def _divisors(n: int) -> List[int]:
    out = [d for d in range(1, int(math.isqrt(n)) + 1) if n % d == 0]
    return sorted(set(out + [n // d for d in out]))


def enumerate_strategies(w: Workload, mcm: MCMArch,
                         max_pp: int = 32,
                         min_layers_per_stage: int = 4) -> List[Strategy]:
    n = mcm.n_devices
    dies = mcm.dies_per_mcm
    moe = w.model.moe
    out = []
    tps = [t for t in _divisors(dies) if w.d_model % t == 0]
    for tp in tps:
        rest1 = n // tp
        # pipeline-stage granularity: embedding/head stages + interleaving
        # overhead make <4 layers per stage impractical
        pps = [p for p in _divisors(rest1)
               if p <= min(max_pp, w.n_layers // min_layers_per_stage)
               or p == 1]
        for pp in pps:
            rest2 = rest1 // pp
            if moe is not None:
                eps = [e for e in _divisors(rest2)
                       if moe.n_experts % e == 0]
            else:
                eps = [1]
            for ep in eps:
                rest3 = rest2 // ep
                cps = [c for c in _divisors(rest3)
                       if c <= 64 and w.seq_len % c == 0 and
                       (c == 1 or w.n_attn_layers > 0)]
                for cp in cps:
                    dp = rest3 // cp
                    if dp > 1 and w.global_batch % dp != 0:
                        continue
                    if pp > 1:
                        n_micro = min(4 * pp,
                                      max(w.global_batch // max(dp, 1), 1))
                        if n_micro < pp:
                            continue
                    else:
                        n_micro = 1
                    s = Strategy(tp=tp, dp=dp, pp=pp, cp=cp, ep=ep,
                                 n_micro=n_micro)
                    if map_intra(w, s, mcm) is not None:
                        out.append(s)
    return out


# ---------------------------------------------------------------------------
# Para-topo evaluation (one design point of the inner search)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class DesignPoint:
    strategy: Strategy
    mcm: MCMArch
    topo: Optional[OITopology]
    sim: SimResult
    cost: float
    fabric: str = "oi"

    @property
    def throughput(self) -> float:
        return self.sim.throughput


def evaluate_point(w: Workload, s: Strategy, mcm: MCMArch,
                   fabric: str = "oi", reuse: bool = True,
                   hw: Optional[HW] = None) -> Optional[DesignPoint]:
    hw = hw or mcm.hw
    mapping = map_intra(w, s, mcm)
    if mapping is None:
        return None
    intra, inter = mapping
    topo = None
    if fabric == "oi":
        vols = traffic_volumes(w, s)
        inter_vols = {p: vols[p] for p, d in inter.items()
                      if d > 1 and vols[p] > 0}
        reuse_pair = None
        if reuse:
            pairs = [pr for pr in reusable_pairs(w, s)
                     if pr[0] in inter_vols and pr[1] in inter_vols]
            reuse_pair = pairs[0] if pairs else None
        alloc = allocate_links(inter_vols, mcm.total_links, reuse_pair)
        inter_deg = {p: d for p, d in inter.items() if d > 1}
        topo = derive_physical_cached(inter_deg, alloc, mcm, mcm.n_mcm, hw,
                                      reuse_pair=reuse_pair)
        if topo is None and reuse_pair is not None:
            alloc = allocate_links(inter_vols, mcm.total_links, None)
            topo = derive_physical_cached(inter_deg, alloc, mcm, mcm.n_mcm,
                                          hw, reuse_pair=None)
        if topo is None and inter_deg:
            return None
    sim = simulate(w, s, mcm, fabric=fabric, topo=topo, reuse=reuse, hw=hw)
    if not sim.feasible:
        return None
    cost = cluster_cost(mcm, topo, fabric=fabric, hw=hw).total
    return DesignPoint(strategy=s, mcm=mcm, topo=topo, sim=sim, cost=cost,
                       fabric=fabric)



"""Compiled-HLO analysis: collective bytes + roofline terms.

``cost_analysis`` gives FLOPs and HBM bytes; collective traffic is NOT in
it, so we parse the post-SPMD HLO text and sum the bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Two aggregates are reported per op kind:
  * result_bytes — sum of output-shape bytes (raw),
  * wire_bytes   — ring-algorithm per-device traffic:
        all-reduce:       2 * size * (n-1)/n
        all-gather:       size * (n-1)/n          (size = result)
        reduce-scatter:   in_size * (n-1)/n  = result * (n-1)
        all-to-all:       size * (n-1)/n
        collective-permute: size
The collective roofline term uses wire_bytes / (chips * link_bw).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

from repro.core.hardware import chip_peaks

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\w+\[[\d,]*\]\S*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(txt: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        # iota format [num_groups,group_size]<=[...]
        return int(m.group(2))
    return 2


@dataclass
class CollectiveStats:
    result_bytes: Dict[str, float] = field(default_factory=dict)
    wire_bytes: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)

    @property
    def total_wire(self) -> float:
        return sum(self.wire_bytes.values())

    @property
    def total_result(self) -> float:
        return sum(self.result_bytes.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    st = CollectiveStats()
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        if "-done(" in line:
            continue   # async pair: count only the -start
        size = _shape_bytes(shape_txt)
        n = _group_size(line)
        if op == "all-reduce":
            wire = 2.0 * size * (n - 1) / max(n, 1)
        elif op == "all-gather":
            wire = size * (n - 1) / max(n, 1)
        elif op == "reduce-scatter":
            wire = size * (n - 1)
        elif op == "all-to-all":
            wire = size * (n - 1) / max(n, 1)
        else:  # collective-permute
            wire = size
        st.result_bytes[op] = st.result_bytes.get(op, 0.0) + size
        st.wire_bytes[op] = st.wire_bytes.get(op, 0.0) + wire
        st.counts[op] = st.counts.get(op, 0) + 1
    return st


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------
def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float, *,
                   device_kind: str) -> Dict[str, float]:
    """All three terms in SECONDS against one chip of ``device_kind``
    (``core.hardware.CHIP_PEAKS``; an unknown kind raises).

    cost_analysis of an SPMD module reports the PER-DEVICE program, and
    wire_bytes are per device too, so each divides by a per-chip peak.
    """
    pk = chip_peaks(device_kind)
    return {
        "compute_s": flops / pk["flops"],
        "memory_s": hbm_bytes / pk["hbm_bw"],
        "collective_s": wire_bytes / pk["ici_bw"],
    }

"""Operations and bytes the algorithms need, from shapes alone.

These are the benchmark's own counts: a model FLOP is a multiply-add
counted as two operations, recomputation is not counted, and a kernel's
bytes are its inputs read once and its outputs written once.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(z: Dict[str, int]) -> int:
    """Weights that take part in a matrix product per token: the
    attention projections and the SwiGLU MLP of every layer, and the
    output head (the embedding is a gather)."""
    d, h, kv, hd, ff = z["d"], z["h"], z["kv"], z["hd"], z["ff"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return z["layers"] * layer + d * z["vocab"]


def train_flops_per_token(z: Dict[str, int], seq: int) -> float:
    """Forward and backward operations per trained token: 6 per matrix
    weight, and causal attention's two products (query i meets i + 1
    keys), three times over for forward and backward."""
    attn = 6.0 * z["layers"] * z["h"] * z["hd"] * (seq + 1)
    return 6.0 * matmul_params(z) + attn


def flash_attention_fwd(b: int, h: int, kv: int, s: int, hd: int,
                        itemsize: int = 4) -> Dict[str, float]:
    """One causal forward call: scores and values, each 2 * hd
    operations per (query, visible key) pair per head; reads q, k, v and
    writes the output and the log-sum-exp row."""
    flops = 2.0 * b * h * hd * s * (s + 1)
    nbytes = itemsize * (2 * b * h * s * hd + 2 * b * kv * s * hd + b * h * s)
    return {"flops": flops, "bytes": float(nbytes)}


def least_time(work: Dict[str, float], peaks: Dict[str, float]
               ) -> Dict[str, float]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = work["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"}

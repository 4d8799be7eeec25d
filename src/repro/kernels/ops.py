"""Public kernel API with backend dispatch and custom VJPs.

Backends:
  * ``pallas``  — the TPU kernels in this package (default on TPU).
  * ``xla``     — blockwise pure-jnp implementations (default elsewhere;
                  also what the CPU dry-run lowers, so HLO stays compact
                  and flash-style memory-efficient via lax.scan).

All train-path ops are differentiable: flash attention and SSD carry
manual/custom VJPs with flash-style recomputation (no O(S^2) residuals).
"""
from __future__ import annotations

import functools
import os
from math import gcd as math_gcd
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_fwd as _fa_pallas
from repro.kernels.moe_gmm import moe_gmm as _gmm_pallas
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

NEG_INF = -1e30


def default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ===========================================================================
# Pallas kernels under a mesh
# ===========================================================================
# GSPMD cannot partition a Mosaic kernel, so under a mesh of more than one
# device (``jax.set_mesh``) each Pallas call runs once per shard inside a
# shard_map: the batch over every axis but ``model``, heads over ``model``,
# each only where it divides.  Whatever does not divide is replicated.
def _kernel_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _mesh_axes(mesh, batch: int, heads: int):
    """(batch axes, head axis) of a kernel call; None where not sharded."""
    data = tuple(a for a in mesh.axis_names if a != "model")
    dsize = 1
    for a in data:
        dsize *= mesh.shape[a]
    b_ax = data if data and batch % dsize == 0 else None
    msize = mesh.shape.get("model", 1)
    h_ax = "model" if msize > 1 and heads % msize == 0 else None
    return b_ax, h_ax


def _per_shard(fn, mesh, in_specs, out_specs, *args):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ===========================================================================
# Flash attention
# ===========================================================================
def _win_value(window, sk, block_k):
    if window is None:
        return jnp.int32(sk + block_k)
    return jnp.asarray(window, jnp.int32)


def _pick_block(s: int, want: int) -> int:
    """Largest divisor of s that is <= want (handles S like 1500)."""
    b = min(want, s)
    while s % b:
        b -= 1
    return b


def _fa_fwd_xla_blocked(q, k, v, window, causal, softcap, scale, block):
    """2D-blocked fwd with a PYTHON loop and STATIC block skipping.

    Skips (q-block, k-block) pairs that are fully masked (causal upper
    triangle, or beyond a static window) — the HLO contains only live
    blocks, so compiled FLOPs reflect the true sub-quadratic cost of
    windowed/causal attention.  Used when ``window`` is static.
    """
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    blkq = _pick_block(sq, block)
    blkk = _pick_block(sk, block)
    nq, nk = sq // blkq, sk // blkk
    f32 = jnp.float32
    qf = q.astype(f32)
    win = window if window is not None else sk + blkk

    o_blocks, lse_blocks = [], []
    for qi in range(nq):
        qb = qf[:, :, qi * blkq:(qi + 1) * blkq]
        rows = qi * blkq + jnp.arange(blkq)[:, None] + (sk - sq)
        acc = jnp.zeros((b, hq, blkq, v.shape[-1]), f32)
        m = jnp.full((b, hq, blkq), NEG_INF, f32)
        l = jnp.zeros((b, hq, blkq), f32)
        for ki in range(nk):
            k_lo, k_hi = ki * blkk, (ki + 1) * blkk - 1
            q_lo, q_hi = (qi * blkq + (sk - sq),
                          qi * blkq + blkq - 1 + (sk - sq))
            if causal and k_lo > q_hi:
                continue                      # above the diagonal
            if k_hi <= q_lo - win:
                continue                      # beyond the window
            kb = jnp.repeat(k[:, :, k_lo:k_lo + blkk].astype(f32), group, 1)
            vb = jnp.repeat(v[:, :, k_lo:k_lo + blkk].astype(f32), group, 1)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            cols = k_lo + jnp.arange(blkk)[None, :]
            mask = (rows - cols) < win
            if causal:
                mask &= cols <= rows
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd",
                                                      p, vb)
            m = m_new
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_blocks.append((acc / lsafe[..., None]).astype(q.dtype))
        lse_blocks.append(m + jnp.log(lsafe))
    return jnp.concatenate(o_blocks, 2), jnp.concatenate(lse_blocks, 2)


def _fa_bwd_xla_blocked(q, k, v, o, lse, do, window, causal, softcap,
                        scale, block):
    """2D-blocked bwd (python loops, static skipping) — see fwd."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    blkq = _pick_block(sq, block)
    blkk = _pick_block(sk, block)
    nq, nk = sq // blkq, sk // blkk
    f32 = jnp.float32
    win = window if window is not None else sk + blkk
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)

    dq_blocks = []
    dk_acc = [None] * nk
    dv_acc = [None] * nk
    for qi in range(nq):
        qb = q[:, :, qi * blkq:(qi + 1) * blkq].astype(f32)
        dob = do[:, :, qi * blkq:(qi + 1) * blkq].astype(f32)
        lseb = lse[:, :, qi * blkq:(qi + 1) * blkq]
        db = delta[:, :, qi * blkq:(qi + 1) * blkq]
        rows = qi * blkq + jnp.arange(blkq)[:, None] + (sk - sq)
        dq_b = jnp.zeros((b, hq, blkq, d), f32)
        for ki in range(nk):
            k_lo, k_hi = ki * blkk, (ki + 1) * blkk - 1
            q_lo, q_hi = (qi * blkq + (sk - sq),
                          qi * blkq + blkq - 1 + (sk - sq))
            if causal and k_lo > q_hi:
                continue
            if k_hi <= q_lo - win:
                continue
            kb = jnp.repeat(k[:, :, k_lo:k_lo + blkk].astype(f32), group, 1)
            vb = jnp.repeat(v[:, :, k_lo:k_lo + blkk].astype(f32), group, 1)
            s_raw = jnp.einsum("bhqd,bhkd->bhqk", qb, kb) * scale
            if softcap:
                t = jnp.tanh(s_raw / softcap)
                s = softcap * t
                dcap = 1.0 - t * t
            else:
                s, dcap = s_raw, None
            cols = k_lo + jnp.arange(blkk)[None, :]
            mask = (rows - cols) < win
            if causal:
                mask &= cols <= rows
            s = jnp.where(mask[None, None], s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])
            dv_q = jnp.einsum("bhqk,bhqd->bhkd", p, dob)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dob, vb)
            ds = p * (dp - db[..., None])
            if dcap is not None:
                ds = ds * dcap
            ds = jnp.where(mask[None, None], ds, 0.0) * scale
            dq_b += jnp.einsum("bhqk,bhkd->bhqd", ds, kb)
            dk_q = jnp.einsum("bhqk,bhqd->bhkd", ds, qb)
            dk_q = dk_q.reshape(b, hkv, group, blkk, d).sum(2)
            dv_q = dv_q.reshape(b, hkv, group, blkk, -1).sum(2)
            dk_acc[ki] = dk_q if dk_acc[ki] is None else dk_acc[ki] + dk_q
            dv_acc[ki] = dv_q if dv_acc[ki] is None else dv_acc[ki] + dv_q
        dq_blocks.append(dq_b)
    zero = jnp.zeros((b, hkv, blkk, d), f32)
    zero_v = jnp.zeros((b, hkv, blkk, v.shape[-1]), f32)
    dk = jnp.concatenate([x if x is not None else zero for x in dk_acc], 2)
    dv = jnp.concatenate([x if x is not None else zero_v for x in dv_acc],
                         2)
    dq = jnp.concatenate(dq_blocks, 2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_fwd_xla(q, k, v, window, causal, softcap, scale, block_k):
    """Blockwise fwd, lax.scan over k blocks.  Returns (o, lse) in fp32."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bk = _pick_block(sk, block_k)
    nk = sk // bk
    win = _win_value(window, sk, bk)
    qf = q.astype(jnp.float32)
    rows = jnp.arange(sq)[:, None] + (sk - sq)

    kb = jnp.moveaxis(k.reshape(b, hkv, nk, bk, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, hkv, nk, bk, v.shape[-1]), 2, 0)

    def step(carry, inp):
        acc, m, l = carry
        ki, kblk, vblk = inp
        kblk = jnp.repeat(kblk.astype(jnp.float32), group, axis=1)
        vblk = jnp.repeat(vblk.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        cols = ki * bk + jnp.arange(bk)[None, :]
        mask = (rows - cols) < win
        if causal:
            mask &= cols <= rows
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((b, hq, sq, v.shape[-1]), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0),
                                  (jnp.arange(nk), kb, vb))
    lsafe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / lsafe[..., None]).astype(q.dtype)
    lse = m + jnp.log(lsafe)
    return o, lse


def _fa_bwd_xla(q, k, v, o, lse, do, window, causal, softcap, scale,
                block_q):
    """Blockwise bwd: single scan over q blocks; dk/dv accumulate in carry."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    bq = _pick_block(sq, block_q)
    nq = sq // bq
    win = _win_value(window, sk, bq)
    f32 = jnp.float32
    kf = jnp.repeat(k.astype(f32), group, axis=1)   # (b,hq,sk,d)
    vf = jnp.repeat(v.astype(f32), group, axis=1)
    cols = jnp.arange(sk)[None, :]

    qb = jnp.moveaxis(q.reshape(b, hq, nq, bq, d), 2, 0).astype(f32)
    dob = jnp.moveaxis(do.reshape(b, hq, nq, bq, -1), 2, 0).astype(f32)
    lseb = jnp.moveaxis(lse.reshape(b, hq, nq, bq), 2, 0)
    # delta_i = rowsum(dO * O)
    delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)
    deltab = jnp.moveaxis(delta.reshape(b, hq, nq, bq), 2, 0)

    def step(carry, inp):
        dk, dv = carry
        qi, qblk, doblk, lseblk, dblk = inp
        s_raw = jnp.einsum("bhqd,bhkd->bhqk", qblk, kf) * scale
        if softcap:
            t = jnp.tanh(s_raw / softcap)
            s = softcap * t
            dcap = (1.0 - t * t)
        else:
            s = s_raw
            dcap = None
        rows = qi * bq + jnp.arange(bq)[:, None] + (sk - sq)
        mask = (rows - cols) < win
        if causal:
            mask &= cols <= rows
        s = jnp.where(mask[None, None], s, NEG_INF)
        p = jnp.exp(s - lseblk[..., None])                   # (b,hq,bq,sk)
        dv_q = jnp.einsum("bhqk,bhqd->bhkd", p, doblk)
        dp = jnp.einsum("bhqd,bhkd->bhqk", doblk, vf)
        ds = p * (dp - dblk[..., None])
        if dcap is not None:
            ds = ds * dcap
        ds = jnp.where(mask[None, None], ds, 0.0) * scale
        dq_b = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
        dk_q = jnp.einsum("bhqk,bhqd->bhkd", ds, qblk)
        # GQA: sum gradients over the head group
        dk_q = dk_q.reshape(b, hkv, group, sk, d).sum(2)
        dv_q = dv_q.reshape(b, hkv, group, sk, -1).sum(2)
        return (dk + dk_q, dv + dv_q), dq_b

    dk0 = jnp.zeros((b, hkv, sk, d), f32)
    dv0 = jnp.zeros(v.shape, f32)
    (dk, dv), dqb = jax.lax.scan(step, (dk0, dv0),
                                 (jnp.arange(nq), qb, dob, lseb, deltab))
    dq = jnp.moveaxis(dqb, 0, 2).reshape(b, hq, sq, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention(q, k, v, window, causal, softcap, scale, block,
                     backend):
    o, _ = _flash_attention_fwd_rule(q, k, v, window, causal, softcap,
                                     scale, block, backend)
    return o


def _static_window(window):
    return window is None or isinstance(window, int)


def _flash_attention_fwd_rule(q, k, v, window, causal, softcap, scale,
                              block, backend):
    if backend == "pallas":
        o, lse = _fa_pallas_on_mesh(q, k, v, window, causal, softcap,
                                    scale)
    elif backend == "xla_blocked" and _static_window(window):
        o, lse = _fa_fwd_xla_blocked(q, k, v, window, causal, softcap,
                                     scale, block)
    else:
        o, lse = _fa_fwd_xla(q, k, v, window, causal, softcap, scale, block)
    return o, (q, k, v, o, lse, window)


def _fa_pallas_on_mesh(q, k, v, window, causal, softcap, scale):
    # the kernel's tiles come from the shapes (``fwd_plan``), not ``block``
    call = functools.partial(_fa_pallas, causal=causal, softcap=softcap,
                             scale=scale)
    mesh = _kernel_mesh()
    if mesh is None:
        return call(q, k, v, window)
    b_ax, h_ax = _mesh_axes(mesh, q.shape[0],
                            math_gcd(q.shape[1], k.shape[1]))
    qs = P(b_ax, h_ax, None, None)
    out_specs = (qs, P(b_ax, h_ax, None))
    if _static_window(window):
        return _per_shard(lambda q_, k_, v_: call(q_, k_, v_, window), mesh,
                          (qs, qs, qs), out_specs, q, k, v)
    return _per_shard(call, mesh, (qs, qs, qs, P()), out_specs,
                      q, k, v, jnp.asarray(window, jnp.int32))


def _flash_attention_bwd_rule(causal, softcap, scale, block, backend, res,
                              do):
    import numpy as np
    q, k, v, o, lse, window = res
    if backend == "xla_blocked" and _static_window(window):
        dq, dk, dv = _fa_bwd_xla_blocked(q, k, v, o, lse, do, window,
                                         causal, softcap, scale, block)
    else:
        dq, dk, dv = _fa_bwd_xla(q, k, v, o, lse, do, window, causal,
                                 softcap, scale, block)
    win_ct = (None if window is None or isinstance(window, int)
              else np.zeros(jnp.shape(window), jax.dtypes.float0))
    return dq, dk, dv, win_ct


def _fa_vjp_fwd(q, k, v, window, causal, softcap, scale, block, backend):
    o, res = _flash_attention_fwd_rule(q, k, v, window, causal, softcap,
                                       scale, block, backend)
    return o, res


_flash_attention.defvjp(_fa_vjp_fwd, _flash_attention_bwd_rule)


def flash_attention(q, k, v, *, window=None, causal=True, softcap=0.0,
                    scale=None, block=128, backend=None):
    """Memory-efficient attention.  q: (B,Hq,S,D); k: (B,Hkv,S,D);
    v: (B,Hkv,S,Dv), Dv free (latent attention); returns (B,Hq,S,Dv).

    ``window`` may be None, an int, or a traced int32 scalar (dynamic
    local/global switching inside a scanned layer stack).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    backend = backend or default_backend()
    return _flash_attention(q, k, v, window, causal, float(softcap),
                            float(scale), int(block), backend)


def decode_attention(q, k_cache, v_cache, pos, *, window=None, softcap=0.0,
                     scale=None):
    """Single-token decode attention.

    q: (B,Hq,1,D); caches: (B,Hkv,Smax,D); pos: () int32 current position
    (number of tokens already in cache, the new token attends to
    cache[0..pos]).  Window masks cache entries older than ``window``.
    Memory-bound: plain jnp is roofline-optimal here (one pass over KV).
    """
    b, hq, _, d = q.shape
    hkv, smax = k_cache.shape[1], k_cache.shape[2]
    if scale is None:
        scale = d ** -0.5
    group = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, group, d)
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, kf) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    cols = jnp.arange(smax)[None, None, None, :]
    mask = cols <= pos
    if window is not None:
        mask &= cols > pos - jnp.asarray(window, jnp.int32)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, hq, 1, -1).astype(q.dtype)


# ===========================================================================
# MoE grouped matmul
# ===========================================================================
# An expert's whole (K, N) weight is one tile where it fits in this many
# bytes, so the consecutive row blocks of an expert fetch it once.
_GMM_WEIGHT_TILE = 8 * 1024 * 1024


def _gmm_pallas_rows(x, w, group_sizes, block_t):
    t, kdim = x.shape
    e, _, n = w.shape
    if kdim * n * w.dtype.itemsize <= _GMM_WEIGHT_TILE:
        bk, bn = kdim, n
    else:
        bk, bn = _pick_block(kdim, 512), _pick_block(n, 512)
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = jnp.arange(t // block_t, dtype=jnp.int32) * block_t
    gid = jnp.minimum(jnp.searchsorted(ends, starts, side="right"), e - 1)
    call = [functools.partial(_gmm_pallas, block_t=block_t, block_n=bn,
                              block_k=bk, interpret=i) for i in (False, True)]
    # the Mosaic kernel on the TPU, the same kernel interpreted elsewhere
    out = jax.lax.platform_dependent(x, w, gid, ends[-1] // block_t,
                                     tpu=call[0], default=call[1])
    # the kernel leaves the rows past the live blocks unwritten
    return jnp.where((jnp.arange(t) < ends[-1])[:, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _moe_gmm(x, w, group_sizes, block_t, backend):
    if backend == "pallas":
        return _gmm_pallas_rows(x, w, group_sizes, block_t)
    return jax.lax.ragged_dot(x, w, group_sizes)


def _moe_gmm_fwd(x, w, group_sizes, block_t, backend):
    return _moe_gmm(x, w, group_sizes, block_t, backend), (x, w, group_sizes)


def _moe_gmm_bwd(block_t, backend, res, dy):
    import numpy as np
    x, w, group_sizes = res
    # XLA's grouped matmuls (dx against the transposed weights, dw over
    # each group's rows)
    _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, group_sizes),
                     x, w)
    dx, dw = vjp(dy)
    return dx, dw, np.zeros(group_sizes.shape, jax.dtypes.float0)


_moe_gmm.defvjp(_moe_gmm_fwd, _moe_gmm_bwd)


def moe_gmm(x, w, group_sizes, *, backend=None, block_t=128):
    """Grouped matmul over expert-sorted rows: rows of group ``g`` (the
    ``group_sizes[g]`` rows after the groups before it) times ``w[g]``;
    rows past ``sum(group_sizes)`` give zeros.  x: (T, K); w: (E, K, N);
    group_sizes: (E,) int32, may be traced.

    pallas: the ``moe_gmm`` kernel, every group a whole number of
    ``block_t`` rows, and only the blocks up to the last group computed
    (T may be sized for the worst case).  xla: ``lax.ragged_dot``.  The
    backward is XLA's ragged matmuls on either backend.
    """
    backend = backend or default_backend()
    return _moe_gmm(x, w, jnp.asarray(group_sizes, jnp.int32), int(block_t),
                    backend)


# ===========================================================================
# SSD (Mamba2)
# ===========================================================================
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, B, C, chunk, backend):
    if backend == "pallas":
        return _ssd_pallas_on_mesh(x, dt, A, B, C, chunk)
    unroll = backend == "xla_blocked"
    y, _ = _ref.ssd_chunked_ref(x, dt, A, B, C, chunk=chunk, unroll=unroll)
    return y


def _ssd_pallas_on_mesh(x, dt, A, B, C, chunk):
    call = functools.partial(_ssd_pallas, chunk=chunk)
    mesh = _kernel_mesh()
    if mesh is None:
        return call(x, dt, A, B, C)
    h, g = x.shape[2], B.shape[2]
    b_ax, h_ax = _mesh_axes(mesh, x.shape[0], h)
    # B/C groups shard with the heads, or are replicated when there is one
    g_ax = h_ax if h_ax and g % mesh.shape["model"] == 0 else None
    if g_ax is None and g != 1:
        h_ax = None
    xs = P(b_ax, None, h_ax, None)
    gs = P(b_ax, None, g_ax, None)
    return _per_shard(call, mesh, (xs, P(b_ax, None, h_ax), P(h_ax), gs, gs),
                      xs, x, dt, A, B, C)


def _ssd_fwd(x, dt, A, B, C, chunk, backend):
    y = _ssd(x, dt, A, B, C, chunk, backend)
    return y, (x, dt, A, B, C)


def _ssd_bwd(chunk, backend, res, dy):
    x, dt, A, B, C = res
    # Flash-style recompute: differentiate the chunked jnp formulation.
    unroll = backend == "xla_blocked"
    def f(x_, dt_, A_, B_, C_):
        y, _ = _ref.ssd_chunked_ref(x_, dt_, A_, B_, C_, chunk=chunk,
                                    unroll=unroll)
        return y
    _, vjp = jax.vjp(f, x, dt, A, B, C)
    return vjp(dy)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, *, chunk=128, backend=None):
    """Mamba2 SSD operator.  See ssd_scan.py for shapes."""
    backend = backend or default_backend()
    return _ssd(x, dt, A, B, C, int(chunk), backend)


# ===========================================================================
# RMSNorm
# ===========================================================================
def rmsnorm(x, w, *, eps=1e-6, weight_offset=0.0, backend=None):
    backend = backend or default_backend()
    if backend == "pallas":
        # fwd-only pallas; bwd recomputes via the jnp formulation
        @jax.custom_vjp
        def _rn(x_, w_):
            call = functools.partial(_rmsnorm_pallas, eps=eps,
                                     weight_offset=weight_offset)
            mesh = _kernel_mesh()
            if mesh is None:
                return call(x_, w_)
            b_ax, _ = _mesh_axes(mesh, x_.shape[0], 1)
            xs = P(b_ax, *([None] * (x_.ndim - 1)))
            return _per_shard(call, mesh, (xs, P(None)), xs, x_, w_)

        def _rn_fwd(x_, w_):
            return _rn(x_, w_), (x_, w_)

        def _rn_bwd(res, dy):
            x_, w_ = res
            _, vjp = jax.vjp(
                lambda a, b: _ref.rmsnorm_ref(a, b, eps=eps,
                                              weight_offset=weight_offset),
                x_, w_)
            return vjp(dy)

        _rn.defvjp(_rn_fwd, _rn_bwd)
        return _rn(x, w)
    return _ref.rmsnorm_ref(x, w, eps=eps, weight_offset=weight_offset)

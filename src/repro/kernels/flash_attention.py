"""Pallas TPU flash-attention forward kernel.

Design (TPU-native, not a CUDA port):
  * the block schedule walks only the (q tile, k tile) blocks that a query
    can see: grid = (B, Hq, live steps), and a scalar-prefetched table
    gives each step its (qi, ki), k innermost within a q tile, so the fp32
    accumulator lives in VMEM scratch across a q tile's k steps.  Blocks
    wholly above the causal diagonal or wholly outside a static window
    take no grid step and no DMA.
  * the tiles come from the shapes (``fwd_plan``): the largest that divide
    S and fit the VMEM budget, so the per-step pipeline overhead stays
    small against each step's MXU work.
  * GQA is expressed in the k/v BlockSpec index_map (kv head = hq*Hkv//Hq)
    so no repeated K/V materialisation ever happens in HBM.
  * a *dynamic* sliding window (an SMEM scalar) lets one compiled kernel
    serve local and global layers (gemma-style alternation inside a
    scanned layer stack): it walks the causal table, skips out-of-window
    blocks with pl.when, and its k/v index_map clamps them onto the q
    tile's first live block, so they fetch nothing.
  * the iota mask is built only on blocks that the diagonal or a window
    edge crosses; interior blocks go straight to the online softmax.  A
    square tile on the causal diagonal runs as its two row halves, so
    its wholly masked upper right quarter is not computed.
  * both products take their operands in the input dtype (bf16 into the
    MXU) with fp32 accumulation; m, l, acc and the log-sum-exp stay fp32.
  * optional logit soft-capping (gemma2) fused into the score computation.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# the scoped VMEM a kernel may use unless it asks for more
_DEFAULT_VMEM = 16 * 1024 * 1024
# the plan's tiles keep their estimated VMEM under this (of the v5e's
# 128 MiB); a kernel whose tiles need more than 3/4 of the default asks
# for the difference
_VMEM_BUDGET = 24 * 1024 * 1024
# preferred tile edges; a plan takes the largest that divides S and fits
_TILES = (1024, 512, 384, 256, 128)


class FwdPlan(NamedTuple):
    """The forward's block schedule: its tiles, the (qi, ki) of each grid
    step of one (batch, head), and the VMEM its tiles need."""
    block_q: int
    block_k: int
    steps: np.ndarray          # (n_live, 2) int32, rows ordered by (qi, ki)
    vmem_bytes: int

    @property
    def n_live(self) -> int:
        return len(self.steps)


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _vmem_bytes(block_q, block_k, d, dv, itemsize) -> int:
    """Double-buffered q, k, v and output tiles (the log-sum-exp column
    pads to 128 lanes), the fp32 scratch, and the fp32 score tiles."""
    tiles = (itemsize * (block_q * _lanes(d) + block_k * _lanes(d)
                         + block_k * _lanes(dv) + block_q * _lanes(dv))
             + 4 * block_q * 128)
    scratch = 4 * block_q * (_lanes(dv) + 2 * 128)
    return 2 * tiles + scratch + 3 * 4 * block_q * _lanes(block_k)


def _tile_edges(s: int):
    """Tile edges that divide ``s``, largest first; ``s`` itself when it
    is no longer than the largest preferred edge."""
    if s <= _TILES[0]:
        return [s]
    edges = [t for t in _TILES if s % t == 0]
    if not edges:
        raise ValueError(f"no tile edge in {_TILES} divides S={s}")
    return edges


def _live_steps(n_q, n_k, block_q, block_k, causal, window) -> np.ndarray:
    """(qi, ki) of every block a query can see: on or below the causal
    diagonal, and inside a static window."""
    qi, ki = np.meshgrid(np.arange(n_q), np.arange(n_k), indexing="ij")
    live = np.ones_like(qi, dtype=bool)
    if causal:
        live &= ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        live &= (ki + 1) * block_k - 1 > qi * block_q - window
    return np.stack([qi[live], ki[live]], axis=1).astype(np.int32)


def fwd_plan(sq: int, d: int, dv: int, dtype, window=None, *, causal=True,
             block_q=None, block_k=None) -> FwdPlan:
    """The forward's schedule for self-attention over ``sq`` positions,
    q/k head size ``d``, v head size ``dv``.

    Tiles: ``block_q``/``block_k`` where given, else the largest square
    tile that divides ``sq`` and keeps the estimated VMEM under the
    budget.  ``window``: a static window (int), or None for none or a
    dynamic one (whose blocks the kernel skips itself).
    """
    itemsize = jnp.dtype(dtype).itemsize
    if block_q is None or block_k is None:
        edges = _tile_edges(sq)
        fits = [t for t in edges
                if _vmem_bytes(t, t, d, dv, itemsize) <= _VMEM_BUDGET]
        edge = fits[0] if fits else edges[-1]
        block_q = block_q or edge
        block_k = block_k or edge
    block_q, block_k = min(block_q, sq), min(block_k, sq)
    if sq % block_q or sq % block_k:
        raise ValueError(f"tiles ({block_q}, {block_k}) do not divide "
                         f"S={sq}")
    steps = _live_steps(sq // block_q, sq // block_k, block_q, block_k,
                        causal, window)
    return FwdPlan(block_q, block_k, steps,
                   _vmem_bytes(block_q, block_k, d, dv, itemsize))


def _and(a, b):
    """Logical and of python bools and traced booleans, folded where
    either side is static."""
    if isinstance(a, bool):
        return b if a else False
    if isinstance(b, bool):
        return a if b else False
    return jnp.logical_and(a, b)


def _not(a):
    return (not a) if isinstance(a, bool) else jnp.logical_not(a)


def _widen(x, n):
    """A (rows, 128) array whose lanes are alike, as (rows, n)."""
    if n == 128:
        return x
    if n % 128 == 0:
        return pltpu.repeat(x, n // 128, axis=1)
    return x[:, :n] if n < 128 else x[:, :1]


def _unpack(code):
    """(qi, ki) of a step from its table entry ``qi << 16 | ki``."""
    return code >> 16, code & 0xFFFF


def _fa_kernel(tab_ref, win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
               acc_ref, m_ref, l_ref, *, scale, softcap, causal, window,
               dynamic, block_q, block_k, dv, n_steps):
    step = pl.program_id(2)
    qi, ki = _unpack(tab_ref[step])
    # a q tile's steps are consecutive in the table
    first = jnp.logical_or(
        step == 0, _unpack(tab_ref[jnp.maximum(step - 1, 0)])[0] != qi)
    last = jnp.logical_or(
        step == n_steps - 1,
        _unpack(tab_ref[jnp.minimum(step + 1, n_steps - 1)])[0] != qi)
    win = win_ref[0] if dynamic else window
    q_start = qi * block_q
    k_start = ki * block_k
    q_end = q_start + block_q - 1
    k_end = k_start + block_k - 1

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the table holds only live blocks, but a dynamic window's are found
    # here; a block needs the mask where the diagonal or a window's edge
    # crosses it
    live = (k_end > q_start - win) if dynamic else True
    crossed = (k_end > q_start) if causal else False
    if win is not None:
        edge = q_end - k_start >= win
        crossed = edge if crossed is False else jnp.logical_or(crossed, edge)

    def update(masked, r0=0, r1=block_q, c1=block_k):
        """The online softmax over rows r0:r1 and columns :c1 of the
        block."""
        nr = r1 - r0
        s = jax.lax.dot_general(q_ref[0, 0, r0:r1], k_ref[0, 0, :c1],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            rows = q_start + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (nr, c1), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (nr, c1), 1)
            mask = cols <= rows if causal else True
            if win is not None:
                mask = _and(mask, (rows - cols) < win)
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[r0:r1]                # (nr, 128), lanes alike
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _widen(m_new, c1))
        l_ref[r0:r1] = l_ref[r0:r1] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        v = v_ref[0, 0, :c1]
        acc_ref[r0:r1] = (acc_ref[r0:r1] * _widen(alpha, dv)
                          + jax.lax.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32))
        m_ref[r0:r1] = m_new

    half = block_q // 2
    if causal and win is None and block_q == block_k and half % 128 == 0:
        # a diagonal block: its upper right quarter is wholly masked
        def diagonal():
            update(True, 0, half, half)
            update(True, half)
        pl.when(crossed)(diagonal)
    else:
        pl.when(_and(live, crossed))(lambda: update(True))
    pl.when(_and(live, _not(crossed)))(lambda: update(False))

    @pl.when(last)
    def _finalize():
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])  # masked rows -> 0
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, :1]
        o_ref[0, 0, ...] = (acc_ref[...] / _widen(l, dv)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, window=None, *, causal=True, softcap=0.0,
                        scale=None, block_q=None, block_k=None,
                        interpret=False):
    """q, k: (B, Hq|Hkv, S, D); v: (B, Hkv, S, Dv); returns (B, Hq, Sq, Dv)
    and the log-sum-exp rows.  Dv may differ from D (latent attention).

    ``window``: None (full), python int (static: its blocks are left out
    of the schedule), or int32 scalar array (dynamic).  ``block_q`` and
    ``block_k`` override the plan's tiles.  Assumes Sq == Sk (training /
    prefill self-attention).
    """
    if window is None or isinstance(window, int):
        return _fwd(q, k, v, jnp.zeros((1,), jnp.int32), causal=causal,
                    softcap=softcap, scale=scale, window=window,
                    dynamic=False, block_q=block_q, block_k=block_k,
                    interpret=interpret)
    return _fwd(q, k, v, jnp.asarray(window, jnp.int32).reshape(1),
                causal=causal, softcap=softcap, scale=scale, window=None,
                dynamic=True, block_q=block_q, block_k=block_k,
                interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "softcap", "scale", "window", "dynamic",
                     "block_q", "block_k", "interpret"))
def _fwd(q, k, v, win, *, causal, softcap, scale, window, dynamic, block_q,
         block_k, interpret):
    b, hq, sq, d = q.shape
    hkv, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    assert sq == sk, "fwd kernel is for self-attention (train/prefill)"
    if scale is None:
        scale = d ** -0.5
    plan = fwd_plan(sq, d, dv, q.dtype, window, causal=causal,
                    block_q=block_q, block_k=block_k)
    bq, bk, n_steps = plan.block_q, plan.block_k, plan.n_live
    tab = jnp.asarray(plan.steps[:, 0] << 16 | plan.steps[:, 1], jnp.int32)

    kernel = functools.partial(
        _fa_kernel, scale=scale, softcap=softcap, causal=causal,
        window=window, dynamic=dynamic, block_q=bq, block_k=bk, dv=dv,
        n_steps=n_steps)

    def q_map(b_, h_, s_, tab_ref, win_ref):
        return b_, h_, _unpack(tab_ref[s_])[0], 0

    def kv_map(b_, h_, s_, tab_ref, win_ref):
        qi, ki = _unpack(tab_ref[s_])
        if dynamic:
            # an out-of-window block takes the q tile's first live block,
            # which the steps after it keep: it fetches nothing
            first = jnp.maximum(qi * bq - win_ref[0] + 1, 0) // bk
            ki = jnp.maximum(ki, first)
        return b_, (h_ * hkv) // hq, ki, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hq, n_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bk, d), kv_map),
            pl.BlockSpec((1, 1, bk, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), q_map),
            # lse as a (block_q, 1) column: a block's last two dims must
            # tile (8, 128) or span the array, and a trailing 1 spans it
            pl.BlockSpec((1, 1, bq, 1), q_map),
        ],
        scratch_shapes=[
            # the running max and sum, each row's value in all 128 lanes
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    vmem = plan.vmem_bytes
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=(None if vmem <= _DEFAULT_VMEM * 3 // 4
                              else vmem + _DEFAULT_VMEM)),
        interpret=interpret,
        name="flash_attention_fwd",
    )(tab, win, q, k, v)
    return o, lse[..., 0]

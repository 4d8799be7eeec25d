"""Sweeps for moe_gmm, ssd_scan, rmsnorm kernels vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


@pytest.mark.parametrize("sizes,bt", [
    ([16, 8, 0, 24], 8),
    ([32, 0, 0, 0], 8),
    ([8, 8, 8, 8], 8),
    ([64, 16, 16, 32], 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(sizes, bt, dtype):
    e, k, n = len(sizes), 64, 96
    t = int(sum(sizes))
    x = jax.random.normal(jax.random.PRNGKey(0), (t, k), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(1), (e, k, n)) * 0.1).astype(
        dtype)
    gids = np.repeat(np.arange(e), np.asarray(sizes) // bt).astype(np.int32)
    out = moe_gmm(x, w, jnp.asarray(gids), block_t=bt, block_n=32,
                  block_k=32, interpret=True)
    r = ref.moe_gmm_ref(x, w, np.asarray(sizes))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("n_live", [0, 3, 5])
def test_moe_gmm_computes_only_the_live_blocks(n_live):
    """Rows sized for more blocks than hold rows: the first ``n_live``
    blocks match the oracle, tiled over K and N as well (the blocks past
    them fetch nothing and are left for the caller to mask)."""
    sizes = [16, 0, 24, 0]                   # 5 blocks of 8 rows
    e, k, n, t = len(sizes), 64, 96, 64      # 8 blocks allocated
    x = jax.random.normal(jax.random.PRNGKey(0), (t, k))
    w = jax.random.normal(jax.random.PRNGKey(1), (e, k, n)) * 0.1
    gids = np.repeat(np.arange(e), np.asarray(sizes) // 8)
    gids = np.concatenate([gids, np.full(8 - len(gids), gids[-1])])
    out = moe_gmm(x, w, jnp.asarray(gids, jnp.int32), n_live, block_t=8,
                  block_n=32, block_k=32, interpret=True)
    r = ref.moe_gmm_ref(x, w, np.asarray(sizes))
    live = 8 * n_live
    np.testing.assert_allclose(np.asarray(out[:live]), np.asarray(r[:live]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bb,s,h,p,g,n,chunk", [
    (1, 32, 2, 8, 1, 16, 8),
    (2, 64, 4, 16, 2, 16, 16),
    (1, 128, 4, 8, 4, 32, 32),   # n_groups == n_heads
    (2, 64, 8, 16, 1, 8, 64),    # single big chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(bb, s, h, p, g, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (bb, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bb, s, h))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = (jax.random.normal(ks[3], (bb, s, g, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (bb, s, g, n)) * 0.3).astype(dtype)
    y = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, _ = ref.ssd_ref(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                        B.astype(jnp.float32), C.astype(jnp.float32))
    tol = 3e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol,
                               atol=tol)


def test_ssd_chunk_invariance():
    """Chunk size must not change the result (property of the algorithm)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    bb, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (bb, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bb, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (bb, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (bb, s, g, n)) * 0.3
    outs = [ssd_scan(x, dt, A, B, C, chunk=c, interpret=True)
            for c in (8, 16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape", [(4, 64), (3, 7, 128), (1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("offset", [0.0, 1.0])
def test_rmsnorm(shape, dtype, offset):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype) * 0.1
    o = rmsnorm(x, w, weight_offset=offset, block_rows=8, interpret=True)
    r = ref.rmsnorm_ref(x, w, weight_offset=offset)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), rtol=tol, atol=tol)

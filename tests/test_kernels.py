"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype, k=0):
    return jax.random.normal(jax.random.PRNGKey(k), shape).astype(dtype)


# ---------------------------------------------------------------------------
# lbp_matmul kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),       # single block
    (256, 384, 128),       # multi k-block (layer accumulation)
    (100, 200, 60),        # ragged -> padding path
    (64, 1024, 64),        # deep contraction, many layers
])
def test_matmul_sweep(m, k, n, dtype, tol):
    x = rand((m, k), dtype, 1)
    w = rand((k, n), dtype, 2)
    out = ops.matmul(x, w, block_m=128, block_n=128, block_k=128,
                     out_dtype=jnp.float32, interpret=True)
    expect = ref.matmul_ref(x, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_matmul_block_shape_invariance():
    x = rand((256, 256), jnp.float32, 3)
    w = rand((256, 256), jnp.float32, 4)
    outs = [np.asarray(ops.matmul(x, w, block_m=bm, block_n=bn, block_k=bk,
                                  interpret=True))
            for bm, bn, bk in [(64, 64, 64), (128, 128, 128), (256, 256, 64)]]
    for o in outs[1:]:
        # different block_k reassociates the layer sum -> small fp drift
        np.testing.assert_allclose(o, outs[0], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (160, 96, 200, 64, 128, 32),    # all three blocks distinct + padding
    (128, 256, 64, 32, 16, 128),    # block_k > block_m/block_n
    (100, 60, 40, 64, 32, 16),      # ragged every dim, non-square blocks
])
def test_matmul_nonsquare_blocks(m, k, n, bm, bn, bk):
    """block_m != block_n != block_k must stay exact vs the oracle."""
    x = rand((m, k), jnp.float32, 11)
    w = rand((k, n), jnp.float32, 12)
    out = ops.matmul(x, w, block_m=bm, block_n=bn, block_k=bk,
                     out_dtype=jnp.float32, interpret=True)
    expect = ref.matmul_ref(x, w, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


def test_matmul_smaller_than_one_block():
    """Shapes far below a single block: the whole product lives in the
    padding path (zero layers are exact by Theorem-1 linearity)."""
    x = rand((7, 5), jnp.float32, 13)
    w = rand((5, 3), jnp.float32, 14)
    out = ops.matmul(x, w, block_m=128, block_n=128, block_k=128,
                     out_dtype=jnp.float32, interpret=True)
    expect = ref.matmul_ref(x, w, out_dtype=jnp.float32)
    assert out.shape == (7, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)
    # degenerate single row/col
    x1 = rand((1, 2), jnp.float32, 15)
    w1 = rand((2, 1), jnp.float32, 16)
    out1 = ops.matmul(x1, w1, interpret=True)
    np.testing.assert_allclose(np.asarray(out1),
                               np.asarray(ref.matmul_ref(x1, w1)),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rglru kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,D,bd,chunk", [
    (1, 8, 32, 32, 8),
    (2, 37, 96, 32, 16),    # ragged seq + channel padding
    (3, 64, 64, 64, 16),    # multi-chunk carry
])
def test_rglru_sweep(B, S, D, bd, chunk):
    a = jax.nn.sigmoid(rand((B, S, D), jnp.float32, 5))
    b = rand((B, S, D), jnp.float32, 6) * 0.1
    h0 = rand((B, D), jnp.float32, 7)
    h, hend = ops.rglru(a, b, h0, block_d=bd, chunk=chunk, interpret=True)
    hr, hendr = ref.rglru_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(hend), np.asarray(hendr),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# weight-stationary sLSTM kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 8, 1, 16, 8),
    (2, 24, 2, 32, 8),      # multi-chunk carry
    (1, 15, 3, 8, 4),       # ragged chunking (falls back to c=5)
])
def test_slstm_sweep(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 9)
    pre = {g: jax.random.normal(ks[i], (B, S, H, hd)) * 0.5
           for i, g in enumerate("zifo")}
    R = {g: jax.random.normal(ks[4 + i], (H, hd, hd)) * hd ** -0.5
         for i, g in enumerate("zifo")}
    state = tuple(jax.random.normal(ks[8], (B, H, hd)) * 0.1
                  for _ in range(3))
    hs, st = ops.slstm(pre, R, state, chunk=chunk, interpret=True)
    hr, sr = ref.slstm_ref(pre, R, state)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hr), rtol=2e-5,
                               atol=2e-5)
    for a, b in zip(st, sr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,H,S,D,bq,bk", [
    (1, 2, 128, 64, 64, 64),
    (2, 3, 200, 64, 64, 64),     # ragged seq -> padding path (causal)
    (1, 1, 256, 128, 128, 64),   # asymmetric blocks
])
def test_flash_causal_sweep(B, H, S, D, bq, bk, dtype, tol):
    q = rand((B, H, S, D), dtype, 8)
    k = rand((B, H, S, D), dtype, 9)
    v = rand((B, H, S, D), dtype, 10)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
    expect = ref.attention_ref(
        q.reshape(B * H, S, D), k.reshape(B * H, S, D),
        v.reshape(B * H, S, D), causal=True).reshape(B, H, S, D)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("S,bq,bk", [
    (96, 96, 64),     # q unpadded, keys padded 96 -> 128 (T % block_k != 0)
    (100, 128, 48),   # T % block_k = 4; q padded too
    (40, 64, 64),     # whole sequence smaller than one KV block
])
def test_flash_key_padding_ragged_T(S, bq, bk):
    """Key/value padding on a T that is NOT a block_k multiple: the padded
    keys sit at positions >= T and the causal mask of every real query row
    must exclude them exactly (no mass leaks into the softmax)."""
    B, H, D = 2, 2, 32
    q = rand((B, H, S, D), jnp.float32, 21)
    k = rand((B, H, S, D), jnp.float32, 22)
    # huge-magnitude values in the *real* tail of k/v: if padded keys were
    # mis-masked the online softmax would visibly shift
    k = k.at[:, :, -1].mul(8.0)
    v = rand((B, H, S, D), jnp.float32, 23)
    out = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
    expect = ref.attention_ref(
        q.reshape(B * H, S, D), k.reshape(B * H, S, D),
        v.reshape(B * H, S, D), causal=True).reshape(B, H, S, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_noncausal():
    B, H, S, D = 1, 2, 128, 64
    q = rand((B, H, S, D), jnp.float32, 11)
    out = ops.flash_attention(q, q, q, causal=False, block_q=64, block_k=64,
                              interpret=True)
    expect = ref.attention_ref(q.reshape(B * H, S, D), q.reshape(B * H, S, D),
                               q.reshape(B * H, S, D),
                               causal=False).reshape(B, H, S, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_xla_flash():
    """Pallas kernel == the models' custom-VJP XLA implementation."""
    from repro.models.attention import flash_attention_xla
    B, H, S, D = 1, 2, 128, 32
    q = rand((B, H, S, D), jnp.float32, 12)
    k = rand((B, H, S, D), jnp.float32, 13)
    v = rand((B, H, S, D), jnp.float32, 14)
    pallas = ops.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_k=64, interpret=True)
    # models layout: (B, S, KV, G, hd) with KV=H, G=1
    qx = q.transpose(0, 2, 1, 3)[:, :, :, None, :]
    kx = k.transpose(0, 2, 1, 3)
    vx = v.transpose(0, 2, 1, 3)
    xla = flash_attention_xla(qx, kx, vx, True, 0, 64, 64)
    xla = xla[:, :, :, 0, :].transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G", [4, 5])          # granite's and qwen3's GQA
@pytest.mark.parametrize("page_size,npp", [(4, 10), (16, 20)])
def test_paged_decode_attention_vs_ref(G, page_size, npp):
    """Kernel == XLA reference over a fragmented pool: depths from 0 to
    view_len - 1 (page boundaries and the last position included), two
    rows sharing pages, idle rows (all trash, stale positions, one past
    the view) first, in the middle and last, and a layer other than 0; a
    40-position view is one block per row, a 320-position view two, with
    the next block and the next live row's first in flight."""
    from repro.kernels.paged_decode_attention_kernel import (
        paged_decode_attention_pallas)
    L, KV, hd, S = 3, 2, 128, 9
    ps, n_pages = page_size, 4 * npp
    rng = np.random.default_rng(G + ps)
    pool = lambda k: rand((L, n_pages + 1, ps, KV, hd), jnp.bfloat16,
                          k).at[:, n_pages].set(0)
    k_pool, v_pool = pool(20 + G), pool(30 + G)
    q = rand((S, KV, G, hd), jnp.bfloat16, 40 + G)
    pos = np.concatenate([[7, 0, ps, 3, ps * npp - 1, ps - 1],
                          rng.integers(0, ps * npp, 2), [10 ** 4]])
    idle = (0, 3, S - 1)
    table = np.full((S, npp), n_pages, np.int32)
    pages = iter(rng.permutation(n_pages).tolist() * 2)
    for row in set(range(S)) - set(idle):
        for col in range(int(pos[row]) // ps + 1):
            table[row, col] = next(pages)
    table[6, :2] = table[4, :2]                # shared pages
    args = (q, k_pool, v_pool, jnp.int32(L - 1), jnp.asarray(table),
            jnp.asarray(pos, jnp.int32))
    out = paged_decode_attention_pallas(*args, interpret=True)
    expect = ref.paged_decode_attention_ref(*args)
    assert out.shape == expect.shape == (S, KV, G, hd)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=2e-2, atol=2e-2)

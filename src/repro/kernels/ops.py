"""Jit'd public wrappers around the Pallas kernels.

Every wrapper:
  * pads inputs to block multiples (zero padding is exact for all three
    kernels: matmul layers, attention KV with -inf masking via extra keys
    being zero... see notes), slices the result back;
  * compiles the kernel for the TPU unless the caller passes
    ``interpret=True`` (the CPU tests do); the mode is never inferred
    from the backend, so a missing chip fails instead of silently
    interpreting;
  * has a pure-jnp oracle in ref.py used by the test sweeps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention_kernel import flash_attention_pallas
from .lbp_matmul_kernel import lbp_matmul_pallas
from .rglru_kernel import rglru_pallas


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "out_dtype", "interpret"))
def matmul(x: jax.Array, w: jax.Array, *, block_m: int = 512,
           block_n: int = 512, block_k: int = 512, out_dtype=None,
           interpret: bool = False) -> jax.Array:
    """Layer-accumulating blocked matmul; pads (M, K, F) to block multiples.

    Zero-padding K adds all-zero layers — exact by Theorem-1 linearity.
    """
    m, k = x.shape
    f = w.shape[1]
    xp = _pad_to(_pad_to(x, 0, block_m), 1, block_k)
    wp = _pad_to(_pad_to(w, 0, block_k), 1, block_n)
    out = lbp_matmul_pallas(xp, wp, block_m=block_m, block_n=block_n,
                            block_k=block_k, out_dtype=out_dtype,
                            interpret=interpret)
    return out[:m, :f]


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def rglru(a: jax.Array, b: jax.Array, h0: jax.Array, *, block_d: int = 512,
          chunk: int = 256, interpret: bool = False):
    """Gated linear recurrence h_t = a_t h_{t-1} + b_t over long sequences.

    Chunks the sequence (kernel holds one chunk in VMEM) and carries h
    between chunks with lax.scan.  Channel dim padded to block_d (padded
    channels recur on zeros — exact).
    Returns (h: (B,S,D), h_end: (B,D)).
    """
    B, S, D = a.shape
    ap = _pad_to(a, 2, block_d)
    bp = _pad_to(b, 2, block_d)
    h0p = _pad_to(h0, 1, block_d)
    Dp = ap.shape[2]

    chunk = min(chunk, S)
    if S % chunk != 0:
        # pad sequence with a=1, b=0 (identity updates)
        pad = (-S) % chunk
        ap = jnp.concatenate([ap, jnp.ones((B, pad, Dp), ap.dtype)], axis=1)
        bp = jnp.concatenate([bp, jnp.zeros((B, pad, Dp), bp.dtype)], axis=1)
    n_chunks = ap.shape[1] // chunk

    def step(h, ab):
        ac, bc = ab
        hs, h_end = rglru_pallas(ac, bc, h, block_d=block_d,
                                 interpret=interpret)
        return h_end, hs

    a_c = ap.reshape(B, n_chunks, chunk, Dp).transpose(1, 0, 2, 3)
    b_c = bp.reshape(B, n_chunks, chunk, Dp).transpose(1, 0, 2, 3)
    h_end, hs = jax.lax.scan(step, h0p, (a_c, b_c))
    h = hs.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, Dp)
    return h[:, :S, :D], h_end[:, :D]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def slstm(pre, R, state, *, chunk: int = 256, interpret: bool = False):
    """Weight-stationary sLSTM over long sequences (chunked, carried state).

    pre: dict z/i/f/o -> (B,S,H,hd); R: dict -> (H,hd,hd);
    state: (c,n,h) each (B,H,hd).
    """
    from .slstm_kernel import slstm_pallas

    B, S, H, hd = pre["z"].shape
    c = min(chunk, S)
    while S % c:
        c -= 1
    n_chunks = S // c

    def step(st, gates):
        hs, st = slstm_pallas(
            {k: v for k, v in zip("zifo", gates)}, R, st,
            interpret=interpret)
        return st, hs

    seqs = tuple(pre[g].reshape(B, n_chunks, c, H, hd).swapaxes(0, 1)
                 for g in "zifo")
    st, hs = jax.lax.scan(step, tuple(state), seqs)
    return hs.swapaxes(0, 1).reshape(B, S, H, hd), st


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Blocked online-softmax attention, (B, H, S, D) layout.

    Query padding rows attend causally to real keys (sliced away); key/value
    padding is masked with an explicit validity mask folded into the causal
    comparison — we pad T to block_k with keys at positions > S which the
    causal mask of every real query row excludes.  For non-causal use, T
    must already be a block multiple (asserted).
    """
    B, H, S, D = q.shape
    T = k.shape[2]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    if not causal:
        assert S % min(block_q, S) == 0 and T % min(block_k, T) == 0, (
            "non-causal path requires block-aligned S/T")
    qf = _pad_to(qf, 1, block_q)
    # key padding sits at positions >= T; causal masking of real rows
    # (row < T <= padded col) excludes it exactly.
    kf = _pad_to(kf, 1, block_k)
    vf = _pad_to(vf, 1, block_k)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out[:, :S].reshape(B, H, S, D)

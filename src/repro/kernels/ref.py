"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(x: jax.Array, w: jax.Array, out_dtype=None) -> jax.Array:
    """f32-accumulated matmul."""
    out = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def rglru_ref(a: jax.Array, b: jax.Array, h0: jax.Array):
    """Sequential scan: h_t = a_t * h_{t-1} + b_t.

    a, b: (B, S, D); h0: (B, D).  Returns (h: (B,S,D), h_end: (B,D)).
    """
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a_t = jnp.swapaxes(a, 0, 1)  # (S, B, D)
    b_t = jnp.swapaxes(b, 0, 1)
    h_end, hs = jax.lax.scan(step, h0, (a_t, b_t))
    return jnp.swapaxes(hs, 0, 1), h_end


def slstm_ref(pre, R, state):
    """Sequential sLSTM oracle (same math as models/xlstm.slstm_block).

    pre: dict z/i/f/o -> (B,S,H,hd); R: dict -> (H,hd,hd);
    state: (c,n,h) each (B,H,hd).  Returns (hs (B,S,H,hd), (c,n,h)).
    """
    def step(carry, gates):
        c, n, h = carry
        pz, pi, pf, po = gates
        z = jnp.tanh(pz + jnp.einsum("bhk,hkv->bhv", h, R["z"]))
        i = jax.nn.sigmoid(pi + jnp.einsum("bhk,hkv->bhv", h, R["i"]))
        f = jax.nn.sigmoid(pf + 1.0 + jnp.einsum("bhk,hkv->bhv", h, R["f"]))
        o = jax.nn.sigmoid(po + jnp.einsum("bhk,hkv->bhv", h, R["o"]))
        c = f * c + i * z
        n = f * n + i
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h), h

    seq = tuple(pre[g].swapaxes(0, 1) for g in ("z", "i", "f", "o"))
    (c, n, h), hs = jax.lax.scan(step, state, seq)
    return hs.swapaxes(0, 1), (c, n, h)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, scale: float | None = None) -> jax.Array:
    """Full-materialization softmax attention. q: (BH,S,D), k/v: (BH,T,D)."""
    BH, S, D = q.shape
    T = k.shape[1]
    scale = float(scale) if scale is not None else float(D) ** -0.5
    s = jnp.einsum("bsd,btd->bst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bst,btd->bsd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_ref(q, k_pool, v_pool, layer, table, pos):
    """One layer's decode attention over the page pool, through XLA.

    Same signature as ``paged_decode_attention_pallas``: q (S, KV, G, hd),
    pools (L, n_pages + 1, page_size, KV, hd), ``layer`` an int32 scalar,
    ``table`` (S, pages_per_slot) the READ map, ``pos`` (S,).  Gathers the
    layer's pages of each row, maps trash-page entries to zeros, and runs
    ``models.attention.decode_attention`` over positions ``0..pos``."""
    from ..models.attention import decode_attention
    S, npp = table.shape
    trash = k_pool.shape[1] - 1

    def view(pool):
        g = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
        g = g[table]                                  # (S, npp, ps, KV, hd)
        g = jnp.where((table == trash)[:, :, None, None, None],
                      jnp.zeros((), g.dtype), g)
        return g.reshape(S, npp * g.shape[2], *g.shape[3:])

    o = decode_attention(q[:, None], view(k_pool), view(v_pool), pos)
    return o[:, 0]

"""Plain float32 forward pass of the dense family, for the ``correct`` check.

Nothing here imports the program.  The model is written from its published
description: pre-norm decoder layers with RMSNorm (scale ``1 + w``), grouped
-query attention (query head ``h`` reads key/value head ``h // (H / KV)``)
with rotary embeddings on the two halves of each head, optional RMSNorm on
each query and key head before the rotation (Qwen3), a SwiGLU MLP, a final
RMSNorm, and an LM head tied to the embedding.  Weights come from
``bench.model``'s seeded draws, one layer at a time, so the reference fits
on the chip beside nothing else.

Several sequences run packed into one row of ``T`` positions: attention is
causal inside each sequence and blind across them.  Every matrix product
runs at ``Precision.HIGHEST`` in float32.

``quant`` computes the same forward one precision step lower, the control
of the check: ``"fp8"`` rounds every weight matrix (per output channel) and
every matrix-product input (per row) to float8 e4m3 with a scale;
``"int8"`` rounds the weight matrices alone to int8 per output channel;
``"w8a16"`` rounds them so and every matrix-product input to bfloat16, as
a program that serves int8 weights to bf16 activations computes.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import model

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256


def _round(x, axis, quant):
    """``x`` rounded to ``quant`` with one scale per slice along ``axis``."""
    if quant == "fp8":
        top, dt = 448.0, jnp.float8_e4m3fn
    elif quant in ("int8", "w8a16"):
        top, dt = 127.0, None
    else:
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    y = x / s
    y = jnp.round(y) if dt is None else y.astype(dt).astype(jnp.float32)
    return y * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, or one precision step lower."""
    if quant == "fp8":
        x = _round(x, -1, quant)
    elif quant == "w8a16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(x, _round(w, 0, quant), precision=HI)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # (T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _layer(cfg, x, w, pos, seg, quant):
    T, d = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    eps = cfg.norm_eps
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rms(x, w["ln1"], eps)
    q = _mm(h, w["wq"], quant).reshape(T, H, hd)
    k = _mm(h, w["wk"], quant).reshape(T, KV, hd)
    v = _mm(h, w["wv"], quant).reshape(T, KV, hd)
    if cfg.qk_norm:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    q = q.reshape(T, KV, H // KV, hd)
    idx = jnp.arange(T)

    def attend(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, Q_CHUNK, 0)
        qi = jax.lax.dynamic_slice_in_dim(idx, start, Q_CHUNK, 0)
        qs = jax.lax.dynamic_slice_in_dim(seg, start, Q_CHUNK, 0)
        s = jnp.einsum("qkgd,tkd->kgqt", qc, k, precision=HI) * hd ** -0.5
        ok = (idx[None, :] <= qi[:, None]) & (seg[None, :] == qs[:, None])
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    o = jax.lax.map(attend, jnp.arange(0, T, Q_CHUNK))
    o = o.reshape(T, H * hd)
    x = x + _mm(o, w["wo"], quant)
    h = _rms(x, w["ln2"], eps)
    g = _mm(h, w["w_gate"], quant)
    u = _mm(h, w["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(cfg, x, rows, top, quant):
    x = _rms(x[rows], top["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return _mm(x, top["embed"].astype(jnp.float32).T, quant)


_draw_layer = jax.jit(model.layer_weights, static_argnums=1)
_draw_top = jax.jit(model.top_weights, static_argnums=1)


def pack(seqs: Sequence[np.ndarray], length: int):
    """Tokens, positions and sequence ids of ``seqs`` packed into one row
    of ``length`` (the tail is a sequence of its own, id -1)."""
    tok = np.zeros(length, np.int32)
    pos = np.zeros(length, np.int32)
    seg = np.full(length, -1, np.int32)
    at = 0
    for i, s in enumerate(seqs):
        n = len(s)
        tok[at:at + n], pos[at:at + n], seg[at:at + n] = s, np.arange(n), i
        at += n
    if at > length:
        raise ValueError(f"{at} positions do not fit in {length}")
    return tok, pos, seg


def logits(cfg, seed: int, seqs: Sequence[np.ndarray], rows: np.ndarray,
           length: int, quant: Optional[str] = None):
    """Logits (len(rows), vocab) at packed positions ``rows`` of ``seqs``,
    weights drawn from ``seed``.  ``rows`` is padded by the caller to a
    fixed count, so one program serves every run."""
    key = model.seed_key(seed)
    tok, pos, seg = (jnp.asarray(a) for a in pack(seqs, length))
    top = _draw_top(key, cfg)
    x = _round(top["embed"][tok].astype(jnp.float32), -1, quant)
    for layer in range(cfg.n_layers):
        x = _layer(cfg, x, _draw_layer(key, cfg, layer), pos, seg, quant)
    return _head(cfg, x, jnp.asarray(rows), top, quant)


def served_rows(prompts: Sequence[np.ndarray],
                served: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Per request: the sequence the reference reads (prompt, then every
    served token but the last) and, offset in the packed row, the positions
    whose logits pick each served token."""
    seqs, rows, at = [], [], 0
    for p, s in zip(prompts, served):
        seq = np.concatenate([p, s[:-1]]).astype(np.int32)
        seqs.append(seq)
        rows.append(at + len(p) - 1 + np.arange(len(s)))
        at += len(seq)
    return seqs, rows

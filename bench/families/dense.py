"""The dense family: pre-norm decoder layers with grouped-query attention
and a SwiGLU MLP, an LM head tied to the embedding.

``model_config`` turns ``bench/configs/<name>.json`` into the program's
``ModelConfig`` (one chip's share: ``tp=1``, no padded heads).

Weights (``bench.common``'s draws): tensor ``t`` of layer ``l`` comes from
``fold_in(fold_in(seed, l + 1), id[t])``; the embedding from ``fold_in(
fold_in(fold_in(seed, 0), block + 1), 0)`` per block of rows, so that only
one block's random bits are held at a time; the final norm from
``fold_in(fold_in(seed, 0), 1)``.  Matrices have standard deviation
``fan_in ** -0.5``, the embedding 0.02.

The reference, written from the published description and importing
nothing of the program: RMSNorm (scale ``1 + w``), grouped-query attention
(query head ``h`` reads key/value head ``h // (H / KV)``) with rotary
embeddings on the two halves of each head, optional RMSNorm on each query
and key head before the rotation (Qwen3), a SwiGLU MLP, a final RMSNorm
and the tied LM head.  It draws one layer at a time, so it fits on the
chip beside nothing else; attention is causal inside each packed sequence
and blind across them; every matrix product runs at ``HIGHEST`` in float32.

The counts are what the mathematics needs, whatever implements it: a
later program that pads, gathers a whole view or converts the head to
float32 does more, and its share of the roofline falls.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import (HI, Q_CHUNK, _mm, _rms, _rope, _round, draw, pack,
                          seed_key)

# tensor ids: part of each tensor's key, so never renumber them
IDS = {"embed": 0, "final_norm": 1, "ln1": 2, "ln2": 3, "wq": 4, "wk": 5,
       "wv": 6, "wo": 7, "q_norm": 8, "k_norm": 9, "w_gate": 10,
       "w_up": 11, "w_down": 12}
NORMS = ("final_norm", "ln1", "ln2", "q_norm", "k_norm")
# the program's ``jax.named_scope``s in this family's programs
SCOPES = ("embed", "attention", "kv_write", "mlp", "lm_head", "page_gather",
          "page_scatter")


def model_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration file's dict."""
    from repro.models.config import ModelConfig
    c = spec["config"]
    if c["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: the dense family is SwiGLU only")
    return ModelConfig(
        name=spec["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"], qk_norm=c["qk_norm"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=True, tp=1, dtype=spec["dtype"])


# ---- weights ---------------------------------------------------------------

def layer_shapes(cfg) -> Dict[str, tuple]:
    d, hd = cfg.d_model, cfg.hd
    s = {"ln1": (d,), "ln2": (d,), "wq": (d, cfg.n_heads * hd),
         "wk": (d, cfg.n_kv_heads * hd), "wv": (d, cfg.n_kv_heads * hd),
         "wo": (cfg.n_heads * hd, d), "w_gate": (d, cfg.d_ff),
         "w_up": (d, cfg.d_ff), "w_down": (cfg.d_ff, d)}
    if cfg.qk_norm:
        s["q_norm"] = (hd,)
        s["k_norm"] = (hd,)
    return s


def _tensor(key, name: str, shape):
    if name in NORMS:
        std = None
    else:
        std = 0.02 if name == "embed" else float(shape[0]) ** -0.5
    return draw(key, IDS[name], shape, std)


def layer_weights(key, cfg, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s tensors (``layer`` may be traced)."""
    k = jax.random.fold_in(key, layer + 1)
    return {n: _tensor(k, n, s) for n, s in layer_shapes(cfg).items()}


def top_weights(key, cfg) -> Dict[str, jax.Array]:
    """The embedding (also the tied LM head) and the final norm."""
    k = jax.random.fold_in(key, 0)
    v, d = cfg.vocab_size, cfg.d_model
    rows = math.gcd(v, 1024)
    embed = jax.lax.map(
        lambda i: _tensor(jax.random.fold_in(k, i + 1), "embed", (rows, d)),
        jnp.arange(v // rows))
    return {"embed": embed.reshape(v, d),
            "final_norm": _tensor(k, "final_norm", (d,))}


def init_weights(cfg):
    """One jitted call: seed key -> the program's parameter tree in bf16,
    made on the device."""
    def init(key):
        # one layer at a time, so that only one layer's random bits are
        # held beside the weights
        blocks = jax.lax.map(lambda l: layer_weights(key, cfg, l),
                             jnp.arange(cfg.n_layers))
        return dict(top_weights(key, cfg), blocks=blocks)
    return jax.jit(init)


# ---- the plain float32 reference -------------------------------------------

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


_draw_layer = jax.jit(layer_weights, static_argnums=1)
_draw_top = jax.jit(top_weights, static_argnums=1)


def reference_logits(cfg, seed: int, seqs: Sequence[np.ndarray],
                     rows: np.ndarray, length: int,
                     quant: Optional[str] = None):
    """Logits (len(rows), vocab) at packed positions ``rows`` of ``seqs``,
    weights drawn from ``seed``.  ``rows`` is padded by the caller to a
    fixed count, so one program serves every run."""
    key = seed_key(seed)
    tok, pos, seg = (jnp.asarray(a) for a in pack(seqs, length))
    top = _draw_top(key, cfg)
    x = _round(top["embed"][tok].astype(jnp.float32), -1, quant)
    for layer in range(cfg.n_layers):
        x = _layer(cfg, x, _draw_layer(key, cfg, layer), pos, seg, quant)
    return _head(cfg, x, jnp.asarray(rows), top, quant)


# ---- operations and bytes --------------------------------------------------

def layer_params(cfg) -> int:
    d, hd = cfg.d_model, cfg.hd
    attn = d * cfg.n_heads * hd * 2 + d * cfg.n_kv_heads * hd * 2
    return attn + 3 * d * cfg.d_ff


def weight_bytes(cfg, itemsize: int = 2) -> int:
    """Every weight once: the layers, the embedding (the tied LM head) and
    the norms."""
    norms = 2 * cfg.d_model + (2 * cfg.hd if cfg.qk_norm else 0)
    per_layer = layer_params(cfg) + norms
    return itemsize * (cfg.n_layers * per_layer
                       + cfg.vocab_size * cfg.d_model + cfg.d_model)


def kv_bytes_per_position(cfg, itemsize: int = 2) -> int:
    return itemsize * 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd


def _step_depths(call):
    """Each step's live depths in a decode call: the rows' depths at its
    first step, one further at each later one."""
    return [[d + i for d in call.depths] for i in range(call.k)]


def decode_step_bytes(cfg, call) -> int:
    """HBM bytes the steps of one decode call need: at each step every
    weight once and each live row's K/V up to its depth (the positions it
    attends to, the new one included, which is written)."""
    return sum(weight_bytes(cfg) + kv_bytes_per_position(cfg) * sum(depths)
               for depths in _step_depths(call))


def _attn_flops(cfg, contexts: int) -> float:
    """Score and value products of one query over ``contexts`` positions,
    all layers."""
    return 4.0 * cfg.n_heads * cfg.hd * contexts * cfg.n_layers


def decode_flops(cfg, call) -> float:
    """The steps of one decode call: live rows only, attention over each
    row's depth, the LM head for each row."""
    per_row = 2.0 * (cfg.n_layers * layer_params(cfg)
                     + cfg.vocab_size * cfg.d_model)
    return sum(per_row * len(depths) + _attn_flops(cfg, sum(depths))
               for depths in _step_depths(call))


def prefill_flops(cfg, length: int) -> float:
    """One prompt of ``length`` real tokens: causal attention, the LM head
    at the last position only."""
    return (2.0 * cfg.n_layers * layer_params(cfg) * length
            + _attn_flops(cfg, length * (length + 1) // 2)
            + 2.0 * cfg.vocab_size * cfg.d_model)

"""Decoder assembly for every assigned family: init, train forward, serving.

Layer stacking uses ``jax.lax.scan`` over stacked parameters (one traced
layer body -> small HLO even at 94 layers) with ``jax.checkpoint`` (remat)
around the body for training.  Heterogeneous stacks (recurrentgemma's
(R,R,A) pattern, xLSTM's 7:1 mLSTM:sLSTM) scan over macro-groups.

Parameters are float32 masters; compute casts to bfloat16 at use (the cast
sits below the FSDP all-gather, so gathers move bf16 bytes).

Caches (serving):
  attention  k/v: (L, B, T, KVe, hd)
  rg-lru     conv: (L_rec, B, W-1, lru), h: (L_rec, B, lru)
  mLSTM      C: (L_m, B, H, hd, hd), n: (L_m, B, H, hd)
  sLSTM      c/n/h: (L_s, B, H, hd)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.ops import paged_decode_attention
from ..sharding.rules import Rules, shard
from .attention import decode_attention, flash_attention_xla
from .config import ModelConfig
from .layers import chunked_cross_entropy, embed_tokens, rms_norm, rope, swiglu_ffn
from .moe import moe_ffn
from .rglru import RGLRUState, recurrent_block
from .xlstm import MLSTMState, SLSTMState, mlstm_block, slstm_block

AUX_COEF = 0.01


def kv_eff(cfg: ModelConfig) -> int:
    """KV head count in parameters and caches (see ModelConfig.kv_param)."""
    return cfg.kv_param


# ===========================================================================
# init
# ===========================================================================

def _norm_init(shape):
    return jnp.zeros(shape, jnp.float32)


def _dense_init(key, shape, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale or fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale)


def _attn_params(key, cfg: ModelConfig, L: int) -> Dict[str, jax.Array]:
    d, hd = cfg.d_model, cfg.hd
    Hp, KVe = cfg.h_padded, kv_eff(cfg)
    ks = jax.random.split(key, 6)
    p = {
        "wq": _dense_init(ks[0], (L, d, Hp * hd)),
        "wk": _dense_init(ks[1], (L, d, KVe * hd)),
        "wv": _dense_init(ks[2], (L, d, KVe * hd)),
        "wo": _dense_init(ks[3], (L, Hp * hd, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((L, hd), jnp.float32)
        p["k_norm"] = jnp.zeros((L, hd), jnp.float32)
    return p


def _ffn_params(key, cfg: ModelConfig, L: int) -> Dict[str, jax.Array]:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    if cfg.is_moe:
        E, ffe = cfg.n_experts, cfg.d_ff
        return {
            "router": _dense_init(ks[0], (L, d, E)),
            "w_gate": _dense_init(ks[1], (L, E, d, ffe)),
            "w_up": _dense_init(ks[2], (L, E, d, ffe)),
            "w_down": _dense_init(ks[3], (L, E, ffe, d)),
        }
    return {
        "w_gate": _dense_init(ks[0], (L, d, cfg.d_ff)),
        "w_up": _dense_init(ks[1], (L, d, cfg.d_ff)),
        "w_down": _dense_init(ks[2], (L, cfg.d_ff, d)),
    }


def _rec_params(key, cfg: ModelConfig, L: int) -> Dict[str, jax.Array]:
    d, lru = cfg.d_model, cfg.lru
    ks = jax.random.split(key, 4)
    return {
        "w_gate": _dense_init(ks[0], (L, d, lru)),
        "w_rec": _dense_init(ks[1], (L, d, lru)),
        "conv_k": _dense_init(ks[2], (L, cfg.conv_width, lru), scale=0.1),
        "conv_b": jnp.zeros((L, lru), jnp.float32),
        "gate_a_w": jnp.ones((L, lru), jnp.float32),
        "gate_a_b": jnp.zeros((L, lru), jnp.float32),
        "gate_x_w": jnp.ones((L, lru), jnp.float32),
        "gate_x_b": jnp.zeros((L, lru), jnp.float32),
        "lambda_param": jnp.full((L, lru), 0.5, jnp.float32),
        "w_out": _dense_init(ks[3], (L, lru, d)),
    }


def _mlstm_params(key, cfg: ModelConfig, shape_prefix) -> Dict[str, jax.Array]:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.zeros(shape_prefix + (d,), jnp.float32),
        "w_q": _dense_init(ks[0], shape_prefix + (d, H * hd)),
        "w_k": _dense_init(ks[1], shape_prefix + (d, H * hd)),
        "w_v": _dense_init(ks[2], shape_prefix + (d, H * hd)),
        "w_i": _dense_init(ks[3], shape_prefix + (d, H)),
        "w_f": _dense_init(ks[3], shape_prefix + (d, H)),
        "w_o": _dense_init(ks[4], shape_prefix + (d, H * hd)),
        "w_out": _dense_init(ks[5], shape_prefix + (H * hd, d)),
    }


def _slstm_params(key, cfg: ModelConfig, L: int) -> Dict[str, jax.Array]:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    ks = jax.random.split(key, 9)
    p = {"ln": jnp.zeros((L, d), jnp.float32),
         "w_out": _dense_init(ks[8], (L, H * hd, d))}
    for t, g in enumerate(("z", "i", "f", "o")):
        p[f"w_{g}"] = _dense_init(ks[t], (L, d, H * hd))
        p[f"r_{g}"] = _dense_init(ks[4 + t], (L, H, hd, hd), scale=hd ** -0.5)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": _dense_init(ks[0], (cfg.vocab_size, cfg.d_model), scale=0.02),
        "final_norm": _norm_init((cfg.d_model,)),
    }
    if cfg.family == "hybrid":
        unit = cfg.block_pattern
        G = cfg.n_layers // len(unit)
        tail = cfg.n_layers - G * len(unit)
        blocks: Dict[str, Any] = {}
        for i, kind in enumerate(unit):
            sub = {"ln_mix": _norm_init((G, cfg.d_model)),
                   "ln_mlp": _norm_init((G, cfg.d_model))}
            if kind == "A":
                sub.update(_attn_params(ks[1 + i], cfg, G))
            else:
                sub.update(_rec_params(ks[1 + i], cfg, G))
            sub.update({f"mlp_{k}": v for k, v in
                        _ffn_params(jax.random.fold_in(ks[1 + i], 7), cfg, G).items()})
            blocks[f"pos{i}"] = sub
        params["groups"] = blocks
        if tail:
            sub = {"ln_mix": _norm_init((tail, cfg.d_model)),
                   "ln_mlp": _norm_init((tail, cfg.d_model))}
            sub.update(_rec_params(ks[6], cfg, tail))
            sub.update({f"mlp_{k}": v for k, v in
                        _ffn_params(ks[7], cfg, tail).items()})
            params["tail"] = sub
    elif cfg.family == "ssm":
        m = cfg.mlstm_per_group
        G = cfg.n_layers // (m + 1)
        params["mlstm"] = _mlstm_params(ks[1], cfg, (G, m))
        params["slstm"] = _slstm_params(ks[2], cfg, G)
    else:
        L = cfg.n_layers
        blocks = {"ln1": _norm_init((L, cfg.d_model)),
                  "ln2": _norm_init((L, cfg.d_model))}
        blocks.update(_attn_params(ks[1], cfg, L))
        blocks.update(_ffn_params(ks[2], cfg, L))
        params["blocks"] = blocks
    return params


# ===========================================================================
# parameter partition specs
# ===========================================================================

def param_specs(cfg: ModelConfig, rules: Rules):
    """PartitionSpec pytree matching init_params (FSDP embed dim + TP)."""
    P = rules.spec
    kv_ax = "kv_heads" if kv_eff(cfg) % cfg.tp == 0 else None

    def attn(prefix=""):
        s = {
            prefix + "wq": P(None, "embed", "heads"),
            prefix + "wk": P(None, "embed", kv_ax),
            prefix + "wv": P(None, "embed", kv_ax),
            prefix + "wo": P(None, "heads", "embed"),
        }
        if cfg.qk_norm:
            s[prefix + "q_norm"] = P(None, None)
            s[prefix + "k_norm"] = P(None, None)
        return s

    def ffn(prefix=""):
        if cfg.is_moe:
            return {
                prefix + "router": P(None, "embed", None),
                prefix + "w_gate": P(None, "expert", "embed", None),
                prefix + "w_up": P(None, "expert", "embed", None),
                prefix + "w_down": P(None, "expert", None, "embed"),
            }
        return {
            prefix + "w_gate": P(None, "embed", "ff"),
            prefix + "w_up": P(None, "embed", "ff"),
            prefix + "w_down": P(None, "ff", "embed"),
        }

    def rec(prefix="", extra_dims=1):
        n = (None,) * extra_dims
        return {
            prefix + "w_gate": P(*n, "embed", "ff"),
            prefix + "w_rec": P(*n, "embed", "ff"),
            prefix + "conv_k": P(*n, None, "ff"),
            prefix + "conv_b": P(*n, "ff"),
            prefix + "gate_a_w": P(*n, "ff"),
            prefix + "gate_a_b": P(*n, "ff"),
            prefix + "gate_x_w": P(*n, "ff"),
            prefix + "gate_x_b": P(*n, "ff"),
            prefix + "lambda_param": P(*n, "ff"),
            prefix + "w_out": P(*n, "ff", "embed"),
        }

    specs: Dict[str, Any] = {
        "embed": P("vocab", "embed"),
        "final_norm": P(None),
    }
    if cfg.family == "hybrid":
        groups = {}
        unit = cfg.block_pattern
        for i, kind in enumerate(unit):
            sub = {"ln_mix": P(None, None), "ln_mlp": P(None, None)}
            sub.update(attn() if kind == "A" else rec())
            sub.update({f"mlp_{k}": v for k, v in ffn().items()})
            groups[f"pos{i}"] = sub
        specs["groups"] = groups
        if cfg.n_layers % len(unit):
            sub = {"ln_mix": P(None, None), "ln_mlp": P(None, None)}
            sub.update(rec())
            sub.update({f"mlp_{k}": v for k, v in ffn().items()})
            specs["tail"] = sub
    elif cfg.family == "ssm":
        n2 = (None, None)
        specs["mlstm"] = {
            "ln": P(*n2, None),
            "w_q": P(*n2, "embed", None),
            "w_k": P(*n2, "embed", None),
            "w_v": P(*n2, "embed", "ff"),
            "w_i": P(*n2, "embed", None),
            "w_f": P(*n2, "embed", None),
            "w_o": P(*n2, "embed", "ff"),
            "w_out": P(*n2, "ff", "embed"),
        }
        sl = {"ln": P(None, None), "w_out": P(None, None, "embed")}
        for g in ("z", "i", "f", "o"):
            sl[f"w_{g}"] = P(None, "embed", None)
            sl[f"r_{g}"] = P(None, None, None, None)
        specs["slstm"] = sl
    else:
        blocks = {"ln1": P(None, None), "ln2": P(None, None)}
        blocks.update(attn())
        blocks.update(ffn())
        specs["blocks"] = blocks
    return specs


# ===========================================================================
# block bodies
# ===========================================================================

def _attention_mix(x, p, cfg: ModelConfig, rules: Rules, positions,
                   cache_kv=None, pos=None, window: int = 0):
    """Pre-norm attention.  cache_kv=(k,v) for serving; returns (y, new_kv).

    Sharding strategy (DESIGN.md §5):
      * train/prefill compute: KV heads are repeated transiently to
        ``cfg.kv_flash`` (a multiple of tp) so the flash tiles shard tp-ways
        even for KV=8/4/1 archs;
      * caches store TRUE KV heads and shard the TIME axis over the model
        axis ("kv_time") — decode attention contracts over time, so each
        device computes a partial (layer!) of the output and the softmax
        normalizer: the paper's layer partition applied to the sequence
        contraction (flash-decoding).  Aggregation = the small all-reduces
        GSPMD emits for the T-reductions.
    """
    B, S, _ = x.shape
    hd, Hp, KVp = cfg.hd, cfg.h_padded, cfg.kv_param
    # named scopes (compiled-HLO op metadata, read back by a profile):
    # "attention" around the projections, scores and output projection,
    # "kv_write" around the cache insert — disjoint, never nested
    q, k, v = _project_qkv(x, p, cfg, rules, positions)

    def _flash(q, k, v):
        KVf = cfg.kv_flash
        r = KVf // KVp
        if r > 1:
            if rules.seq is not None:
                # under sequence parallelism, gather the seq dim BEFORE the
                # head repeat: repeating a seq-sharded tensor into a
                # head-sharded layout makes GSPMD fall back to involuntary
                # full replication (§Perf iteration).
                k = shard(k, rules, "batch", None, None, None)
                v = shard(v, rules, "batch", None, None, None)
            k = jnp.repeat(k, r, axis=2)
            v = jnp.repeat(v, r, axis=2)
        k = shard(k, rules, "batch", None, "kv_heads", None)
        v = shard(v, rules, "batch", None, "kv_heads", None)
        qg = q.reshape(B, S, KVf, Hp // KVf, hd)
        o = flash_attention_xla(qg, k, v, True, window)
        return o.reshape(B, S, Hp, hd)

    new_kv = None
    if cache_kv is not None:
        ck, cv = cache_kv   # (B, Tc, KVp, hd), time sharded over "kv_time"
        Tc = ck.shape[1]
        # windowed archs keep a ring buffer of size window: slot s holds the
        # most recent absolute position congruent to s (k/v carry RoPE, so
        # attention is slot-order invariant).
        ring = window > 0 and Tc <= window
        if S == 1:  # decode: insert, then LBP-over-time attention
            wpos = pos % Tc if ring else pos
            with jax.named_scope("kv_write"):
                ck = jax.vmap(
                    lambda c, kk, pp: jax.lax.dynamic_update_slice_in_dim(
                        c, kk, pp, 0))(ck, k[:, 0:1].astype(ck.dtype), wpos)
                cv = jax.vmap(
                    lambda c, vv, pp: jax.lax.dynamic_update_slice_in_dim(
                        c, vv, pp, 0))(cv, v[:, 0:1].astype(cv.dtype), wpos)
            with jax.named_scope("attention"):
                qg = q.reshape(B, S, KVp, Hp // KVp, hd)
                # ring: every slot is inside the window by construction ->
                # only the "not written yet" mask (t <= pos) applies.
                o = decode_attention(qg, ck, cv, pos,
                                     window=0 if ring else window)
                o = o.reshape(B, S, Hp, hd)
        else:       # prefill: write true-KV cache, attend with repeats
            from .tuning import TUNING
            with jax.named_scope("kv_write"):
                kc, vc = k, v
                if TUNING.cache_write_constraint:
                    # match the cache's (batch, kv_time) layout before the
                    # insert: without this GSPMD falls back to involuntary
                    # full replication when resharding into the
                    # time-sharded cache.
                    kc = shard(kc, rules, "batch", "kv_time", None, None)
                    vc = shard(vc, rules, "batch", "kv_time", None, None)
                if S >= Tc:   # windowed cache keeps the trailing Tc
                    # positions, rolled so slot == absolute_position % Tc
                    # (ring invariant for decode continuation; no-op when
                    # Tc divides S).
                    ck = jnp.roll(kc[:, S - Tc:], S % Tc,
                                  axis=1).astype(ck.dtype)
                    cv = jnp.roll(vc[:, S - Tc:], S % Tc,
                                  axis=1).astype(cv.dtype)
                else:
                    ck = jax.lax.dynamic_update_slice_in_dim(
                        ck, kc.astype(ck.dtype), 0, 1)
                    cv = jax.lax.dynamic_update_slice_in_dim(
                        cv, vc.astype(cv.dtype), 0, 1)
            with jax.named_scope("attention"):
                o = _flash(q, k, v)
        new_kv = (ck, cv)
    else:
        with jax.named_scope("attention"):
            o = _flash(q, k, v)
    return _project_out(o, x, p, cfg, rules), new_kv


def _project_qkv(x, p, cfg: ModelConfig, rules: Rules, positions):
    """Pre-norm q/k/v projections, q/k RMSNorm and RoPE: (B, S, Hp, hd)
    queries and (B, S, KVp, hd) keys and values, under "attention"."""
    B, S, _ = x.shape
    hd, Hp, KVp = cfg.hd, cfg.h_padded, cfg.kv_param
    with jax.named_scope("attention"):
        h = rms_norm(x, p["ln1"] if "ln1" in p else p["ln_mix"],
                     cfg.norm_eps)
        q = jnp.einsum("bsd,dk->bsk", h,
                       p["wq"].astype(h.dtype)).reshape(B, S, Hp, hd)
        k = jnp.einsum("bsd,dk->bsk", h,
                       p["wk"].astype(h.dtype)).reshape(B, S, KVp, hd)
        v = jnp.einsum("bsd,dk->bsk", h,
                       p["wv"].astype(h.dtype)).reshape(B, S, KVp, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        return shard(q, rules, "batch", None, "heads", None), k, v


def _project_out(o, x, p, cfg: ModelConfig, rules: Rules):
    """Attention output (B, S, Hp, hd) -> residual update (B, S, d), under
    "attention"."""
    B, S, d = x.shape
    hd, Hp = cfg.hd, cfg.h_padded
    with jax.named_scope("attention"):
        o = shard(o, rules, "batch", None, "heads", None)
        # LBP row-parallel out-projection: contraction over model-sharded
        # heads.
        from . import lbp_linear
        from .tuning import reduce_pref_dtype
        if lbp_linear.applicable(rules):
            return lbp_linear.lbp_row_parallel(
                o.reshape(B, S, Hp * hd).astype(x.dtype),
                p["wo"].astype(x.dtype), rules)
        y = jnp.einsum("bshk,hkD->bsD", o.astype(x.dtype),
                       p["wo"].reshape(Hp, hd, d).astype(x.dtype),
                       preferred_element_type=reduce_pref_dtype(x.dtype))
        return shard(y.astype(x.dtype), rules, "batch", "seq", None)


def _ffn_mix(x, p, cfg: ModelConfig, rules: Rules, prefix=""):
    """Pre-norm FFN (dense SwiGLU or MoE). Returns (y, aux)."""
    with jax.named_scope("mlp"):
        ln = p["ln2"] if "ln2" in p else p["ln_mlp"]
        h = rms_norm(x, ln, cfg.norm_eps)
        if cfg.is_moe:
            return moe_ffn(h, p[prefix + "router"], p[prefix + "w_gate"],
                           p[prefix + "w_up"], p[prefix + "w_down"], rules,
                           experts_per_token=cfg.experts_per_token,
                           capacity_factor=cfg.capacity_factor)
        y = swiglu_ffn(h, p[prefix + "w_gate"], p[prefix + "w_up"],
                       p[prefix + "w_down"], rules)
        return y, jnp.zeros((), jnp.float32)


# ===========================================================================
# forward (training / no-cache): returns final hidden + aux
# ===========================================================================

def forward_hidden(params, cfg: ModelConfig, rules: Rules, tokens,
                   prefix_embeds=None, remat: bool = True):
    B = tokens.shape[0]
    x = embed_tokens(tokens, params["embed"], rules)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None].repeat(B, 0)
    x = shard(x, rules, "batch", "seq", None)

    if cfg.family == "hybrid":
        x, aux = _hybrid_stack(x, params, cfg, rules, positions, remat)
    elif cfg.family == "ssm":
        x, aux = _ssm_stack(x, params, cfg, rules, remat)
    else:
        x, aux = _uniform_stack(x, params, cfg, rules, positions, remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def _uniform_stack(x, params, cfg, rules, positions, remat):
    def body(carry, layer_p):
        x, aux = carry
        a, _ = _attention_mix(x, layer_p, cfg, rules, positions)
        x = x + a
        f, al = _ffn_mix(x, layer_p, cfg, rules)
        return (x + f, aux + al), None

    fn = jax.checkpoint(body) if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    return x, aux


def _hybrid_stack(x, params, cfg, rules, positions, remat):
    unit = cfg.block_pattern

    def group_body(carry, group_p):
        x, aux = carry
        for i, kind in enumerate(unit):
            p = group_p[f"pos{i}"]
            if kind == "A":
                a, _ = _attention_mix(x, p, cfg, rules, positions,
                                      window=cfg.window)
            else:
                a, _ = recurrent_block(
                    rms_norm(x, p["ln_mix"], cfg.norm_eps), p, rules)
            x = x + a
            f, al = _ffn_mix(x, p, cfg, rules, prefix="mlp_")
            x = x + f
            aux = aux + al
        return (x, aux), None

    fn = jax.checkpoint(group_body) if remat else group_body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                               params["groups"])
    if "tail" in params:
        def tail_body(carry, p):
            x, aux = carry
            a, _ = recurrent_block(
                rms_norm(x, p["ln_mix"], cfg.norm_eps), p, rules)
            x = x + a
            f, al = _ffn_mix(x, p, cfg, rules, prefix="mlp_")
            return (x + f, aux + al), None
        fn = jax.checkpoint(tail_body) if remat else tail_body
        (x, aux), _ = jax.lax.scan(fn, (x, aux), params["tail"])
    return x, aux


def _ssm_stack(x, params, cfg, rules, remat):
    H, hd = cfg.n_heads, cfg.hd

    def group_body(carry, group_p):
        x, aux = carry
        mp, sp = group_p

        def m_body(xc, lp):
            h = rms_norm(xc, lp["ln"], cfg.norm_eps)
            y, _ = mlstm_block(h, lp, rules, n_heads=H, head_dim=hd,
                               chunk=cfg.mlstm_chunk)
            return xc + y, None

        x, _ = jax.lax.scan(m_body, x, mp)
        h = rms_norm(x, sp["ln"], cfg.norm_eps)
        y, _ = slstm_block(h, sp, rules, n_heads=H, head_dim=hd)
        return (x + y, aux), None

    fn = jax.checkpoint(group_body) if remat else group_body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)),
                               (params["mlstm"], params["slstm"]))
    return x, aux


# ===========================================================================
# loss
# ===========================================================================

def loss_fn(params, cfg: ModelConfig, rules: Rules, batch,
            remat: bool = True):
    """Next-token CE over the token region (prefix positions excluded)."""
    tokens = batch["tokens"]
    prefix = batch.get("prefix_embeds")
    hidden, aux = forward_hidden(params, cfg, rules, tokens, prefix, remat)
    Pfx = 0 if prefix is None else prefix.shape[1]
    h_tok = hidden[:, Pfx:, :]
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:], jnp.float32),
         jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
    ce = chunked_cross_entropy(h_tok, params["embed"], labels, rules,
                               mask=mask)
    return ce + AUX_COEF * aux


# ===========================================================================
# serving: cache init, prefill, decode
# ===========================================================================

def init_cache(cfg: ModelConfig, B: int, T: int, dtype=jnp.bfloat16):
    hd, KVe = cfg.hd, kv_eff(cfg)
    if cfg.family == "hybrid":
        unit = cfg.block_pattern
        G = cfg.n_layers // len(unit)
        tail = cfg.n_layers - G * len(unit)
        cache: Dict[str, Any] = {}
        Tw = min(T, cfg.window) if cfg.window else T
        for i, kind in enumerate(unit):
            if kind == "A":
                cache[f"pos{i}"] = (
                    jnp.zeros((G, B, Tw, KVe, hd), dtype),
                    jnp.zeros((G, B, Tw, KVe, hd), dtype))
            else:
                cache[f"pos{i}"] = RGLRUState(
                    conv=jnp.zeros((G, B, cfg.conv_width - 1, cfg.lru),
                                   jnp.float32),
                    h=jnp.zeros((G, B, cfg.lru), jnp.float32))
        if tail:
            cache["tail"] = RGLRUState(
                conv=jnp.zeros((tail, B, cfg.conv_width - 1, cfg.lru),
                               jnp.float32),
                h=jnp.zeros((tail, B, cfg.lru), jnp.float32))
        return cache
    if cfg.family == "ssm":
        m = cfg.mlstm_per_group
        G = cfg.n_layers // (m + 1)
        H, hd = cfg.n_heads, cfg.hd
        return {
            "mlstm": MLSTMState(
                C=jnp.zeros((G, m, B, H, hd, hd), jnp.float32),
                n=jnp.zeros((G, m, B, H, hd), jnp.float32),
                lf_acc=jnp.zeros((G, m, B, H), jnp.float32)),
            "slstm": SLSTMState(
                c=jnp.zeros((G, B, H, hd), jnp.float32),
                n=jnp.zeros((G, B, H, hd), jnp.float32),
                h=jnp.zeros((G, B, H, hd), jnp.float32)),
        }
    L = cfg.n_layers
    return {"k": jnp.zeros((L, B, T, KVe, hd), dtype),
            "v": jnp.zeros((L, B, T, KVe, hd), dtype)}


def cache_specs(cfg: ModelConfig, rules: Rules):
    """PartitionSpec pytree matching init_cache.

    KV caches shard their TIME axis over the model dim ("kv_time"): the
    decode attention contracts over time, so this is the paper's layer
    partition on the sequence axis (each device owns k_i cache slices and
    contributes one partial layer of the attention output)."""
    P = rules.spec
    kv = P(None, "batch", "kv_time", None, None)
    if cfg.family == "hybrid":
        unit = cfg.block_pattern
        specs: Dict[str, Any] = {}
        rec = RGLRUState(conv=P(None, "batch", None, "ff"),
                         h=P(None, "batch", "ff"))
        for i, kind in enumerate(unit):
            specs[f"pos{i}"] = (kv, kv) if kind == "A" else rec
        if cfg.n_layers % len(unit):
            specs["tail"] = rec
        return specs
    if cfg.family == "ssm":
        return {
            "mlstm": MLSTMState(C=P(None, None, "batch", None, None, "ff"),
                                n=P(None, None, "batch", None, None),
                                lf_acc=P(None, None, "batch", None)),
            "slstm": SLSTMState(c=P(None, "batch", None, None),
                                n=P(None, "batch", None, None),
                                h=P(None, "batch", None, None)),
        }
    return {"k": kv, "v": kv}


def prefill(params, cfg: ModelConfig, rules: Rules, tokens, cache,
            prefix_embeds=None, last_index=None):
    """Run the full prompt, filling ``cache``; returns (cache, last_logits).

    ``last_index`` (B,) optionally picks a per-row position for the
    returned logits instead of the common last one — the serving engine
    right-pads mixed-length prompts to one batch and reads each row's
    logits at its own true last token (indices count from the start of
    ``prefix_embeds`` when given).  Causality keeps the pad positions out
    of every real position's attention, so row r's logits match an
    unpadded length-``last_index[r]+1`` prefill.
    """
    B = tokens.shape[0]
    with jax.named_scope("embed"):
        x = embed_tokens(tokens, params["embed"], rules)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None].repeat(B, 0)
    x = shard(x, rules, "batch", "seq", None)
    x, cache = _stack_with_cache(x, params, cfg, rules, positions, cache,
                                 pos=None)
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if last_index is None:
            last = x[:, -1]
        else:
            last = x[jnp.arange(B), jnp.asarray(last_index, jnp.int32)]
        logits = jnp.einsum("bd,vd->bv", last.astype(jnp.float32),
                            params["embed"].astype(jnp.float32))
        return cache, shard(logits, rules, "batch", "vocab")


def decode_step(params, cfg: ModelConfig, rules: Rules, token, pos, cache):
    """One token: token (B, 1) int32, pos (B,) int32 -> (logits, cache)."""
    B = token.shape[0]
    with jax.named_scope("embed"):
        x = embed_tokens(token, params["embed"], rules)
    positions = pos[:, None]
    x = shard(x, rules, "batch", None, None)
    x, cache = _stack_with_cache(x, params, cfg, rules, positions, cache,
                                 pos=pos)
    return _decode_logits(x, params, cfg, rules), cache


def _decode_logits(x, params, cfg: ModelConfig, rules: Rules):
    """Final norm and the tied head: (B, 1, d) -> (B, 1, V) float32."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            params["embed"].astype(jnp.float32))
        return shard(logits, rules, "batch", None, "vocab")


def paged_decode_step(params, cfg: ModelConfig, rules: Rules, token, pos,
                      pool, table, write_table):
    """One token per row against the physical page pool, in place.

    token (S, 1) int32, pos (S,) int32; ``pool`` {"k", "v"} leaves
    (L, n_pages + 1, page_size, KV, hd), the last page the trash page;
    ``table`` the (S, pages_per_slot) READ page map, ``write_table`` the
    WRITE map.  Returns (logits (S, 1, V), pool).  The layer scan carries
    (x, pool): each layer writes its new K/V row through ``write_table``
    and attends over the row's pages through ``table`` (dense causal
    stacks only: the paged plane's families)."""
    with jax.named_scope("embed"):
        x = embed_tokens(token, params["embed"], rules)
    x = shard(x, rules, "batch", None, None)

    def body(carry, inp):
        x, pool = carry
        p, layer = inp
        q, k, v = _project_qkv(x, p, cfg, rules, pos[:, None])
        pool = _paged_kv_write(pool, layer, k[:, 0], v[:, 0], pos,
                               write_table)
        with jax.named_scope("attention"):
            S, _, Hp, hd = q.shape
            KVp = cfg.kv_param
            o = paged_decode_attention(
                q[:, 0].reshape(S, KVp, Hp // KVp, hd), pool["k"],
                pool["v"], layer, table, pos)
        x = x + _project_out(o.reshape(S, 1, Hp, hd), x, p, cfg, rules)
        f, _ = _ffn_mix(x, p, cfg, rules)
        return (x + f, pool), None

    (x, pool), _ = jax.lax.scan(
        body, (x, pool),
        (params["blocks"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return _decode_logits(x, params, cfg, rules), pool


def _paged_kv_write(pool, layer, k, v, pos, write_table):
    """Write row b's new K/V (S, KV, hd) at ``(layer, write_table[b, pos_b
    // page_size], pos_b % page_size)``.  Rows whose write lands on the
    trash page (idle rows, write-protected shared pages) write zeros, so
    racing duplicates agree and the trash page stays all-zero."""
    with jax.named_scope("kv_write"):
        n_phys, page_size = pool["k"].shape[1:3]
        col = jnp.minimum(pos // page_size, write_table.shape[1] - 1)
        page = jnp.take_along_axis(write_table, col[:, None], axis=1)[:, 0]
        off = pos % page_size
        live = (page != n_phys - 1)[:, None, None]

        def put(leaf, row):
            row = jnp.where(live, row, 0).astype(leaf.dtype)
            return leaf.at[layer, page, off].set(row)
        return {"k": put(pool["k"], k), "v": put(pool["v"], v)}


def _stack_with_cache(x, params, cfg, rules, positions, cache, pos):
    """Layer stack threading serving state (scan xs=params+cache, ys=cache)."""
    decode = pos is not None
    B = x.shape[0]
    if pos is None:
        pos_arr = jnp.zeros((B,), jnp.int32)
    else:
        pos_arr = pos

    if cfg.family == "hybrid":
        unit = cfg.block_pattern

        def group_body(x, inp):
            group_p, group_c = inp
            new_c = {}
            for i, kind in enumerate(unit):
                p, c = group_p[f"pos{i}"], group_c[f"pos{i}"]
                if kind == "A":
                    a, nkv = _attention_mix(x, p, cfg, rules, positions,
                                            cache_kv=c, pos=pos_arr,
                                            window=cfg.window)
                    new_c[f"pos{i}"] = nkv
                else:
                    a, ns = recurrent_block(
                        rms_norm(x, p["ln_mix"], cfg.norm_eps), p, rules,
                        state=RGLRUState(*c))
                    new_c[f"pos{i}"] = ns
                x = x + a
                f, _ = _ffn_mix(x, p, cfg, rules, prefix="mlp_")
                x = x + f
            return x, new_c

        group_cache = {k: v for k, v in cache.items() if k != "tail"}
        x, new_cache = jax.lax.scan(group_body, x,
                                    (params["groups"], group_cache))
        if "tail" in params:
            def tail_body(x, inp):
                p, c = inp
                a, ns = recurrent_block(
                    rms_norm(x, p["ln_mix"], cfg.norm_eps), p, rules,
                    state=RGLRUState(*c))
                x = x + a
                f, _ = _ffn_mix(x, p, cfg, rules, prefix="mlp_")
                return x + f, ns
            x, tail_cache = jax.lax.scan(tail_body, x,
                                         (params["tail"], cache["tail"]))
            new_cache["tail"] = tail_cache
        return x, new_cache

    if cfg.family == "ssm":
        H, hd = cfg.n_heads, cfg.hd

        def group_body(x, inp):
            (mp, sp), (mc, sc) = inp

            def m_body(xc, lp_lc):
                lp, lc = lp_lc
                h = rms_norm(xc, lp["ln"], cfg.norm_eps)
                y, ns = mlstm_block(h, lp, rules, n_heads=H, head_dim=hd,
                                    chunk=cfg.mlstm_chunk,
                                    state=MLSTMState(*lc))
                return xc + y, ns

            x, new_mc = jax.lax.scan(m_body, x, (mp, mc))
            h = rms_norm(x, sp["ln"], cfg.norm_eps)
            y, new_sc = slstm_block(h, sp, rules, n_heads=H, head_dim=hd,
                                    state=SLSTMState(*sc))
            return x + y, (new_mc, new_sc)

        x, (new_m, new_s) = jax.lax.scan(
            group_body, x,
            ((params["mlstm"], params["slstm"]),
             (cache["mlstm"], cache["slstm"])))
        return x, {"mlstm": new_m, "slstm": new_s}

    def body(x, inp):
        layer_p, (ck, cv) = inp
        a, nkv = _attention_mix(x, layer_p, cfg, rules, positions,
                                cache_kv=(ck, cv), pos=pos_arr)
        x = x + a
        f, _ = _ffn_mix(x, layer_p, cfg, rules)
        return x + f, nkv

    x, (nk, nv) = jax.lax.scan(body, x, (params["blocks"],
                                         (cache["k"], cache["v"])))
    return x, {"k": nk, "v": nv}

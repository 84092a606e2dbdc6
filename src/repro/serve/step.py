"""Serving steps: batched prefill, single-token decode, paged decode.

``decode_32k`` / ``long_500k`` dry-run cells lower ``decode_step`` (one new
token against a seq_len cache); ``prefill_32k`` lowers ``prefill``.
Caches shard their time axis over the model dim (LBP on the sequence
contraction — see models/transformer.cache_specs).

The continuous-batching engine (``serve.engine``) consumes these step
builders through the jit caches below — one decode compilation per
(config, rules) no matter how many requests are served.  The *paged*
builders run the same layers against the physical page pool
(``serve.engine.cache_pool``) in place: attention reads each row's pages
through the page table and each layer writes only its new K/V row, in the
SAME jitted call, so a paged decode step is still ONE dispatch.
``greedy_generate`` is the reference oracle for both planes: under greedy
decoding the engines must reproduce its outputs token-for-token
(tests/test_serve_engine.py enforces this).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..models.config import ModelConfig
from ..sharding.rules import Rules


def make_prefill_step(cfg: ModelConfig, rules: Rules):
    def step(params, tokens, cache, prefix_embeds=None):
        return T.prefill(params, cfg, rules, tokens, cache,
                         prefix_embeds=prefix_embeds)
    return step


def make_decode_step(cfg: ModelConfig, rules: Rules):
    def step(params, token, pos, cache):
        logits, cache = T.decode_step(params, cfg, rules, token, pos, cache)
        with jax.named_scope("lm_head"):
            next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, logits, cache
    return step


def make_paged_decode_step(cfg: ModelConfig, rules: Rules):
    """One decode step against a paged pool, in place: each layer writes
    its new K/V row into the row's page and attends over the row's pages
    straight from the pool — one dispatch, no per-slot view.  ``pool``
    leaves are (L, n_pages + 1, page_size, ...); ``table`` is the
    (n_slots, pages_per_slot) int32 READ page map and ``write_table`` the
    WRITE map (identical unless prefix sharing masks shared pages to the
    trash page — the copy-on-write discipline lives entirely in which map
    the read and the write use)."""
    def step(params, token, pos, pool, table, write_table):
        logits, pool = T.paged_decode_step(params, cfg, rules, token, pos,
                                           pool, table, write_table)
        with jax.named_scope("lm_head"):
            next_token = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_token, logits, pool
    return step


def make_paged_decode_scan(cfg: ModelConfig, rules: Rules, k: int):
    """``k`` fused decode steps on the paged plane in one dispatch.  The
    scan carries the pool (the page maps are fixed for the whole stretch —
    the engine claims every page the k steps will write *before*
    dispatching); each step writes one K/V row per layer and row."""
    step = make_paged_decode_step(cfg, rules)

    def run(params, tok, pos, pool, table, write_table):
        def body(carry, _):
            tok, pos, pool = carry
            nxt, _, pool = step(params, tok[:, None], pos, pool, table,
                                write_table)
            return (nxt, pos + 1, pool), nxt

        (tok, pos, pool), stack = jax.lax.scan(body, (tok, pos, pool),
                                               None, length=k)
        return pool, stack, tok, pos
    return run


# ---------------------------------------------------------------------------
# jit caches: Rules hashes by its axis table (mesh is excluded from hash),
# so the cache key includes id(mesh) to keep two meshes with identical axis
# names from sharing a compiled step.
# ---------------------------------------------------------------------------

_STEP_CACHE: Dict[Tuple[str, ModelConfig, Rules, int], Any] = {}


def _cached(kind: str, cfg: ModelConfig, rules: Rules, builder):
    key = (kind, cfg, rules, id(rules.mesh))
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = jax.jit(builder(cfg, rules))
    return _STEP_CACHE[key]


def cached_prefill_step(cfg: ModelConfig, rules: Rules):
    return _cached("prefill", cfg, rules, make_prefill_step)


def cached_decode_step(cfg: ModelConfig, rules: Rules):
    return _cached("decode", cfg, rules, make_decode_step)


def greedy_generate(params, cfg: ModelConfig, rules: Rules, prompt,
                    max_new: int = 16, return_logits: bool = False):
    """Reference generation loop.

    This is the oracle the serving engine is checked against: one request,
    exact-length cache, greedy argmax at every step.  With
    ``return_logits`` it also returns the (B, max_new, V) logits each
    token was picked from, so a mismatch can be read as a near-tie or not.
    """
    B, S = prompt.shape
    cache = T.init_cache(cfg, B, S + max_new)
    cache, logits = cached_prefill_step(cfg, rules)(params, prompt, cache)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    out, seen = [tok], [logits[:, None]]
    pos = jnp.full((B,), S, jnp.int32)
    step = cached_decode_step(cfg, rules)
    for _ in range(max_new - 1):
        nxt, logits, cache = step(params, tok, pos, cache)
        tok = nxt[:, None]
        out.append(tok)
        seen.append(logits)
        pos = pos + 1
    tokens = jnp.concatenate(out, axis=1)
    if return_logits:
        return tokens, jnp.concatenate(seen, axis=1)
    return tokens

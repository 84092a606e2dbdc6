"""Operations and bytes of the dense family's serving work, from shapes.

These count what the mathematics needs, whatever implements it: a later
program that pads, gathers a whole view or converts the head to float32
does more, and its share of the roofline falls.  ``cfg`` is the program's
``ModelConfig`` (true head counts; nothing is padded on one chip).
"""

from __future__ import annotations

from typing import Iterable


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


def decode_step_bytes(cfg, depths: Iterable[int]) -> int:
    """HBM bytes one decode step needs: every weight once, each live row's
    K/V up to its depth (the positions it attends to, the new one
    included, which is written)."""
    return weight_bytes(cfg) + kv_bytes_per_position(cfg) * sum(depths)


def _attn_flops(cfg, contexts: int) -> float:
    """Score and value products of one query over ``contexts`` positions,
    all layers."""
    return 4.0 * cfg.n_heads * cfg.hd * contexts * cfg.n_layers


def decode_flops(cfg, depths: Iterable[int]) -> float:
    """One decode step: live rows only, attention over each row's depth,
    the LM head for each row."""
    depths = list(depths)
    per_row = 2.0 * (cfg.n_layers * layer_params(cfg)
                     + cfg.vocab_size * cfg.d_model)
    return per_row * len(depths) + _attn_flops(cfg, sum(depths))


def prefill_flops(cfg, length: int) -> float:
    """One prompt of ``length`` real tokens: causal attention, the LM head
    at the last position only."""
    return (2.0 * cfg.n_layers * layer_params(cfg) * length
            + _attn_flops(cfg, length * (length + 1) // 2)
            + 2.0 * cfg.vocab_size * cfg.d_model)

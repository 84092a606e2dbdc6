"""A configuration file as the program runs it, and its weights from a seed.

``model_config`` turns ``bench/configs/<name>.json`` into the program's
``ModelConfig`` (one chip's share: ``tp=1``, no padded heads).

The weights are the benchmark's own, never the program's: the plain
reference has to rebuild them without anything the program made.  Every
tensor of layer ``l`` comes from its own key, ``fold_in(fold_in(seed, l +
1), tensor)``, through integer bits and one multiply, so ``layer_weights``
gives the same bf16 values whether one layer is drawn (the reference, layer
by layer) or all are drawn at once inside one jitted call (``init_weights``,
the program's tree).  Matrices are normal with standard deviation
``fan_in ** -0.5``, the embedding 0.02: 16 random bits pick one of 65,536
quantiles of the standard normal from a fixed table, times one constant.
Their tails reach 4.3 standard deviations, so a per-channel rounding to
int8 errs on them about as it would on trained weights, where a uniform
draw (tails at 1.7) would hide it.  Norm offsets are uniform within +-0.1
(the program applies ``1 + w``).
"""

from __future__ import annotations

import functools
import json
import math
import pathlib
import statistics
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# tensor ids: part of each tensor's key, so never renumber them
_IDS = {"embed": 0, "final_norm": 1, "ln1": 2, "ln2": 3, "wq": 4, "wk": 5,
        "wv": 6, "wo": 7, "q_norm": 8, "k_norm": 9, "w_gate": 10,
        "w_up": 11, "w_down": 12}
NORMS = ("final_norm", "ln1", "ln2", "q_norm", "k_norm")


def load_config(name: str) -> dict:
    return json.loads((ROOT / "configs" / f"{name}.json").read_text())


def model_config(spec: dict):
    """The program's ``ModelConfig`` for a configuration file's dict."""
    from repro.models.config import ModelConfig
    c = spec["config"]
    if spec["family"] != "dense" or c["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: only dense SwiGLU models run here")
    return ModelConfig(
        name=spec["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"], qk_norm=c["qk_norm"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=True, tp=1, dtype=spec["dtype"])


def seed_key(seed: int):
    """A PRNG key for any whole number, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


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


@functools.lru_cache(maxsize=None)
def normal_quantiles() -> np.ndarray:
    """The standard normal's quantiles at (i + 1/2) / 65536, float32."""
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv((i + 0.5) / 65536) for i in range(65536)],
                    np.float32)


def _draw(key, name: str, shape, std: float):
    """Values in bf16 from 16 random bits each: normal of standard
    deviation ``std`` (norm offsets: uniform within +-0.1)."""
    bits = jax.random.bits(jax.random.fold_in(key, _IDS[name]), shape,
                           jnp.uint32) >> 16
    if name in NORMS:
        x = (bits.astype(jnp.float32) - 32767.5) * (0.1 / 32768.0)
    else:
        x = jnp.asarray(normal_quantiles())[bits.astype(jnp.int32)] * std
    return x.astype(jnp.bfloat16)


def _std(name: str, shape) -> float:
    return 0.02 if name == "embed" else float(shape[0]) ** -0.5


def layer_weights(key, cfg, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s tensors (``layer`` may be traced)."""
    k = jax.random.fold_in(key, layer + 1)
    return {n: _draw(k, n, s, _std(n, s)) for n, s in layer_shapes(cfg).items()}


def top_weights(key, cfg) -> Dict[str, jax.Array]:
    """The embedding (also the tied LM head) and the final norm.  The
    embedding is drawn in blocks of rows, each from its own key, so that
    only one block's random bits are held at a time."""
    k = jax.random.fold_in(key, 0)
    v, d = cfg.vocab_size, cfg.d_model
    rows = math.gcd(v, 1024)
    std = _std("embed", (v, d))
    embed = jax.lax.map(
        lambda i: _draw(jax.random.fold_in(k, i + 1), "embed", (rows, d), std),
        jnp.arange(v // rows))
    return {"embed": embed.reshape(v, d),
            "final_norm": _draw(k, "final_norm", (d,), 0.0)}


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

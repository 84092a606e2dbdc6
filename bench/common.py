"""What every model family shares: the seeded bf16 draw, the packing of
checked requests into one row, and the float32 helpers of the references.

The weights are the benchmark's own, never the program's: a family's plain
reference has to rebuild them without anything the program made.  Every
tensor comes from its own key, a fold of the seed's key by the family's
layout (layer, block of rows) and at last by the tensor's id, through
integer bits and one multiply, so one tensor drawn alone (the reference,
layer by layer) and all drawn at once inside one jitted call (the
program's tree) give the same values.  An id is part of each tensor's
key, so ids are never renumbered: the dense family's tensors hold 0-12,
and a new family's own tensors take ids from 13 up, so that none of them
draws the same bits as a dense tensor of the same layer.

Matrices are normal with a given standard deviation: 16 random bits pick
one of 65,536 quantiles of the standard normal from a fixed table, times
one constant.  Their tails reach 4.3 standard deviations, so a per-channel
rounding to int8 errs on them about as it would on trained weights, where
a uniform draw (tails at 1.7) would hide it.  Norm offsets are uniform
within +-0.1 (the program applies ``1 + w``).
"""

from __future__ import annotations

import functools
import statistics
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256      # a packed row's length is a multiple of this


def seed_key(seed: int):
    """A PRNG key for any whole number, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def normal_quantiles() -> np.ndarray:
    """The standard normal's quantiles at (i + 1/2) / 65536, float32."""
    inv = statistics.NormalDist().inv_cdf
    return np.array([inv((i + 0.5) / 65536) for i in range(65536)],
                    np.float32)


def draw(key, tensor_id: int, shape, std: Optional[float]):
    """Values in bf16 from 16 random bits each, from ``key`` folded by
    ``tensor_id``: normal of standard deviation ``std``, or with ``std``
    None a norm's offsets, uniform within +-0.1."""
    bits = jax.random.bits(jax.random.fold_in(key, tensor_id), shape,
                           jnp.uint32) >> 16
    if std is None:
        x = (bits.astype(jnp.float32) - 32767.5) * (0.1 / 32768.0)
    else:
        x = jnp.asarray(normal_quantiles())[bits.astype(jnp.int32)] * std
    return x.astype(jnp.bfloat16)


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


# The float32 forward's pieces.  ``quant`` computes one precision step
# lower, the control of the check: ``"fp8"`` rounds every weight matrix
# (per output channel) and every matrix-product input (per row) to float8
# e4m3 with a scale; ``"int8"`` rounds the weight matrices alone to int8
# per output channel; ``"w8a16"`` rounds them so and every matrix-product
# input to bfloat16, as a program that serves int8 weights to bf16
# activations computes.

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

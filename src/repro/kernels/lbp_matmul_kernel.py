"""Pallas TPU kernel: layer-accumulating blocked matmul (LBP at VMEM level).

The paper's layer decomposition ``C = sum_i A[:,K_i] @ B[K_i,:]`` maps onto
the TPU memory hierarchy as the k-innermost blocked matmul: each K grid step
computes one *layer* of a ``(bm, bn)`` output tile and accumulates it into a
float32 VMEM scratch accumulator — the kernel-level form of the paper's
"aggregate layers lazily" (the accumulator is written back to HBM exactly
once, at the last layer).  Pipelining across the K grid is the paper's
*simultaneous start* mode: the DMA fetching layer j+1's operands overlaps the
MXU computing layer j.

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost ("arbitrary" semantics so the
accumulator carries across steps; M/N are parallel).  Blocks default to
(512, 512, 512): MXU-aligned (multiples of 128) and a VMEM working set of
  x(512x512xbf16) + w(512x512xbf16) + acc(512x512xf32) = 0.5+0.5+1.0 MB
plus double buffering ~ 3 MB << 16 MB v5e VMEM.

Validated against ``ref.matmul_ref`` with ``interpret=True`` (CPU executes
the kernel body; the TPU is the deployment target).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one LBP layer of this output tile: A[:, K_k] @ B[K_k, :]
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def lbp_matmul_pallas(
    x: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 512,
    block_n: int = 512,
    block_k: int = 512,
    out_dtype: Optional[jnp.dtype] = None,
    interpret: bool = False,
) -> jax.Array:
    """``x @ w`` with layer-accumulating VMEM tiling.

    x: (M, K), w: (K, F).  M, K, F must be divisible by the block sizes
    (the ops.py wrapper pads).  Accumulation is always float32.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    out_dtype = out_dtype or x.dtype
    n_k = k // block_k

    grid = (m // block_m, n // block_n, n_k)
    kernel = functools.partial(_matmul_kernel, n_k=n_k)

    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=interpret,
    )(x, w)

"""Pallas TPU kernel: one layer's decode attention read in place from the
physical page pool.

The paged serving plane keeps K and V as ``(L, n_pages + 1, page_size, KV,
hd)`` leaves (the last page is the all-zero trash page) and a READ page
table ``(S, pages_per_slot)`` per decode row.  This kernel attends one new
query token per row over that row's live pages only: the layer index, the
table and the positions arrive as scalar prefetch, the pool leaves stay in
HBM whole (``pl.ANY``), and each row DMAs the pages at or below
``pos // page_size``, ``pages_per_block`` pages per compute block, double
buffered across blocks and across rows.  An idle row (its first page is
the trash page) reads nothing and outputs zeros.  No per-slot view is
gathered and no slice of the pool is taken outside the kernel.

Per block the K/V pages ``(pages, page_size, KV, hd)`` fold into a
``(T·KV, hd)`` matrix whose row ``t·KV + h`` holds position ``t`` of KV
head ``h``.  One product of all ``KV·G`` query heads against it gives every
head's scores; the entries that pair a query head with another KV head, and
positions past ``pos``, are masked out before an online softmax in float32.
The product does KV times the work one head needs, which decode can afford
(the MXU idles on one query row per head), and it keeps every operand a
plain 2-D tile-aligned matrix.

Grid ``(S,)``, sequential ("arbitrary"): the block after the current one,
or the next row's first block, is in flight while the current one computes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 256     # positions per compute block (pages_per_block × page)


def _live_pages(pos, page_size: int, pages_per_slot: int):
    """Pages a row at ``pos`` attends over (its depth's page and those
    below; a position past the view is clamped to the table)."""
    return jnp.minimum(pos // page_size, pages_per_slot - 1) + 1


def _kernel(layer_ref, table_ref, pos_ref,        # scalar prefetch (SMEM)
            q_ref, k_hbm, v_hbm,                 # inputs
            o_ref,                               # output
            kbuf, vbuf, sem, m_ref, l_ref, acc_ref, slot_ref,   # scratch
            *, page_size: int, pages_per_block: int, n_kv: int,
            group: int, scale: float):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    pages_per_slot = table_ref.shape[1]
    tokens = pages_per_block * page_size
    layer = layer_ref[0]
    trash = k_hbm.shape[1] - 1

    def n_blocks(row):
        """Blocks of ``row``; none for an idle row (its first page is the
        trash page: every request holds its first page)."""
        n = _live_pages(pos_ref[row], page_size, pages_per_slot)
        return jnp.where(table_ref[row, 0] == trash, 0,
                         (n + pages_per_block - 1) // pages_per_block)

    def pages_in(row, blk):
        """Live pages of ``row`` in block ``blk``: pages past the row's
        depth are neither fetched nor waited on."""
        n = _live_pages(pos_ref[row], page_size, pages_per_slot)
        return jnp.clip(n - blk * pages_per_block, 0, pages_per_block)

    def each_copy(row, blk, slot, op):
        def body(i, carry):
            page = table_ref[row, blk * pages_per_block + i]
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf)):
                op(pltpu.make_async_copy(src.at[layer, page], dst.at[slot, i],
                                         sem.at[slot]))
            return carry
        jax.lax.fori_loop(0, pages_in(row, blk), body, 0)

    def start(row, blk, slot):
        each_copy(row, blk, slot, lambda cp: cp.start())

    def wait(row, blk, slot):
        each_copy(row, blk, slot, lambda cp: cp.wait())

    def start_next_row(slot):
        nxt = jnp.minimum(b + 1, n_rows - 1)
        pl.when(jnp.logical_and(b + 1 < n_rows, n_blocks(nxt) > 0))(
            lambda: start(nxt, 0, slot))

    @pl.when(b == 0)
    def _first():
        # pages past a row's depth keep what the buffers held: zeros here,
        # or pages fetched earlier (finite), never uninitialized bits that
        # a masked probability of 0 would still multiply into NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0
        pl.when(n_blocks(0) > 0)(lambda: start(0, 0, 0))

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # a position past the view attends the whole view (else the last
    # block's unfetched tail would count as live)
    pos = jnp.minimum(pos_ref[b], pages_per_slot * page_size - 1)
    q = q_ref[0]                                             # (KV·G, hd)
    heads = q.shape[0]
    hd = q.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (heads, tokens * n_kv), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (heads, tokens * n_kv), 1)
    same_head = rows // group == cols % n_kv
    nb = n_blocks(b)
    pl.when(nb == 0)(lambda: start_next_row(slot_ref[0]))

    def block(j, slot):
        nxt = 1 - slot

        pl.when(j + 1 < nb)(lambda: start(b, j + 1, nxt))
        pl.when(j + 1 == nb)(lambda: start_next_row(nxt))

        wait(b, j, slot)
        k = kbuf[slot].reshape(tokens * n_kv, hd)
        v = vbuf[slot].reshape(tokens * n_kv, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (KV·G, T·KV)
        ok = jnp.logical_and(same_head, j * tokens + cols // n_kv <= pos)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, nb, block, slot_ref[0])
    # a live row's denominator is at least 1 (its largest score's term);
    # an idle row's is 0 and its output 0, as the reference's over zeros
    l = l_ref[...]
    o_ref[0] = jnp.where(l > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0),
                         0.0).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pool, v_pool, layer, table, pos, *,
                                  interpret: bool = False):
    """q: (S, KV, G, hd); k_pool/v_pool: (L, n_pages + 1, page_size, KV,
    hd); layer: int32 scalar; table: (S, pages_per_slot) int32 READ map;
    pos: (S,) int32 -> (S, KV, G, hd).  Row ``b`` attends positions
    ``0..pos[b]`` of its pages; trash-page entries read the trash page,
    which the write path keeps all-zero."""
    S, KV, G, hd = q.shape
    page_size = k_pool.shape[2]
    assert k_pool.shape[3:] == (KV, hd) and v_pool.shape == k_pool.shape
    pages_per_slot = table.shape[1]
    pages_per_block = max(1, min(pages_per_slot, BLOCK_TOKENS // page_size))
    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_block=pages_per_block,
        n_kv=KV, group=G, scale=float(hd) ** -0.5)
    row = pl.BlockSpec((1, KV * G, hd), lambda b, *_: (b, 0, 0))
    buf = pltpu.VMEM((2, pages_per_block, page_size, KV, hd), k_pool.dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((KV * G, 1), jnp.float32),     # running max
                pltpu.VMEM((KV * G, 1), jnp.float32),     # denominator
                pltpu.VMEM((KV * G, hd), jnp.float32),    # accumulator
                pltpu.SMEM((1,), jnp.int32),              # buffer in use
            ]),
        out_shape=jax.ShapeDtypeStruct((S, KV * G, hd), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      pos.astype(jnp.int32), q.reshape(S, KV * G, hd).astype(k_pool.dtype),
      k_pool, v_pool)
    return out.reshape(S, KV, G, hd).astype(q.dtype)

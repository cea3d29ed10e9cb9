"""Paged decode attention Pallas TPU kernel.

One decode token per row attends to the K/V its page table maps, read
straight from the serving block pool: no dense ``(rows, max_len)`` view is
gathered first.  Row ``r`` reads pages ``0 .. ceil(len[r] / page_size) - 1``
of its table, and nothing past them; an idle row (``len 0``) reads none.

Layout.  The pool keeps the layout every writer uses, ``(num_blocks,
page_size, Hkv, hd)``; the kernel sees it as ``(num_blocks, page_size *
Hkv, hd)``, the same bytes on the chip (the pool's tiles are ``(Hkv, hd)``
slabs, Hkv a multiple of 8), so one page is one contiguous DMA.  Row ``t *
Hkv + h`` of a page is position ``t``, kv head ``h``.

Work.  The grid walks the rows in order (one row per step); inside a step
a loop walks the row's pages ``pages_per_block`` at a time, double
buffered: each block's DMAs are started before the previous block is
scored, and the last block of a row starts the first block of the next
live row, so the copy engine never waits on a row boundary.  A block's
``(Hq, hd) @ (hd, T * Hkv)`` product scores every q head against every kv
head of the block at once; each q head keeps only its own GQA group's
columns (8x the MXU work of a per-head slice, and no lane shuffles).
Masked columns (other groups, positions at or past the row's length) go
to ``NEG_INF``.  The softmax is online (running max and sum) and the
values accumulate, all in float32; q, K and V enter the MXU in the pool's
dtype.  Buffers are zeroed once, so the unread tail of a row's last block
holds finite values that a zero weight cancels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.vmem import compiler_params

NEG_INF = -1e30
PAGES_PER_BLOCK = 16


def vmem_bytes(pages_per_block: int, page_rows: int, hq: int, hd: int,
               itemsize: int) -> int:
    """VMEM the kernel holds: K and V blocks, double buffered, the q and
    out tiles (double buffered by the grid pipeline) and one block's f32
    scores and weights."""
    cols = pages_per_block * page_rows
    return (2 * 2 * cols * hd * itemsize + 2 * 2 * hq * hd * itemsize
            + 2 * hq * cols * 4)


def _kernel(lens_ref, table_ref, next_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, *, page_size: int, group: int):
    row = pl.program_id(0)
    rows = pl.num_programs(0)
    max_pages = table_ref.shape[0] // rows
    page_rows = k_hbm.shape[1]  # page_size * Hkv
    hkv = page_rows // page_size
    ppb = k_buf.shape[1] // page_rows
    span = ppb * page_size  # positions per block

    def dma(r, blk, slot, i, kv):
        """The copy of page ``i`` of block ``blk`` of row ``r`` into buffer
        ``slot`` of K (kv 0) or V (kv 1)."""
        page = table_ref[r * max_pages + blk * ppb + i]
        hbm, buf = (k_hbm, k_buf) if kv == 0 else (v_hbm, v_buf)
        at = pl.multiple_of(i * page_rows, page_rows)
        return pltpu.make_async_copy(hbm.at[page],
                                     buf.at[slot, pl.ds(at, page_rows)],
                                     sems.at[kv, slot])

    def each_page(r, blk, slot, op):
        """``op`` on the K and V copies of the block's pages that lie
        inside the row's length."""
        n = jnp.minimum(pl.cdiv(lens_ref[r], page_size) - blk * ppb, ppb)

        def body(i, carry):
            for kv in (0, 1):
                op(dma(r, blk, slot, i, kv))
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def start(r, blk, slot):
        each_page(r, blk, slot, lambda c: c.start())

    def wait(r, blk, slot):
        each_page(r, blk, slot, lambda c: c.wait())

    @pl.when(row == 0)
    def _first():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        pl.when(next_ref[0] < rows)(lambda: start(next_ref[0], 0, 0))

    length = lens_ref[row]

    @pl.when(length == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        nblk = pl.cdiv(length, span)
        after = next_ref[row + 1]
        q = q_ref[0]  # (Hq, hd), already scaled
        hq, hd = q.shape
        shape = (hq, ppb * page_rows)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # column t * Hkv + h belongs to q head i's group when h == i // g
        head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        own = col % hkv == head // group

        def block(blk, carry):
            m, l, acc = carry
            slot = slot_ref[0]
            # prefetch the row's next block, or after its last the first
            # block of the next live row
            more = blk + 1 < nblk
            pl.when(more | (after < rows))(lambda: start(
                jnp.where(more, row, after), jnp.where(more, blk + 1, 0),
                1 - slot))
            wait(row, blk, slot)
            k, v = k_buf[slot], v_buf[slot]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            live = own & (col < (length - blk * span) * hkv)
            s = jnp.where(live, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            slot_ref[0] = 1 - slot
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, nblk, block, (jnp.full((hq, 1), NEG_INF, jnp.float32),
                             jnp.zeros((hq, 1), jnp.float32),
                             jnp.zeros((hq, hd), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    lengths: jax.Array,
    page_table: jax.Array,
    next_live: jax.Array,
    *,
    pages_per_block: int = PAGES_PER_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Single-token attention over a paged K/V pool.

    q: (B, Hq, hd), scaled by ``hd ** -0.5``; k_pages, v_pages:
    (num_blocks, page_size, Hkv, hd); lengths: (B,) int32 positions each
    row attends to (0: idle row, output zeros); page_table: (B, max_pages)
    int32 block ids; next_live: (B + 1,) int32, entry ``i`` the first row
    at or after ``i`` whose length is not 0 (B where none is).  Returns
    (B, Hq, hd) in q's dtype.
    """
    b, hq, hd = q.shape
    num_blocks, page_size, hkv, _ = k_pages.shape
    page_rows = page_size * hkv
    ppb = min(pages_per_block, page_table.shape[1])
    k = k_pages.reshape(num_blocks, page_rows, hd)
    v = v_pages.reshape(num_blocks, page_rows, hd)
    tile = pl.BlockSpec((1, hq, hd), lambda r, *_: (r, 0, 0))
    buf = pltpu.VMEM((2, ppb * page_rows, hd), k.dtype)
    need = vmem_bytes(ppb, page_rows, hq, hd, k.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, group=hq // hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=tile,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, hq, hd), q.dtype),
        # rows run in order: a row's last block prefetches the next row's
        compiler_params=compiler_params(("arbitrary",), need),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths, page_table.reshape(-1), next_live, q, k, v)

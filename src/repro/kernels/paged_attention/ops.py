"""Public wrapper around the paged decode attention kernel, and what it
compiles for."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.vmem import LANES

# the pool's bf16 tiles on the chip are (8, 128) slabs of (Hkv, hd): with
# Hkv a multiple of 8, a page's (page_size, Hkv, hd) is (page_size * Hkv,
# hd) rows without a relayout
KV_HEAD_TILE = 8


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def supports(head_dim: int, num_kv_heads: int, dtype) -> bool:
    """What the kernel compiles for on the chip: a bf16 pool whose pages
    are lane-aligned rows (``hd`` a multiple of 128) read in place."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and head_dim % LANES == 0
            and num_kv_heads % KV_HEAD_TILE == 0)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    lengths: jax.Array, page_table: jax.Array, *,
                    interpret: bool | None = None) -> jax.Array:
    """q (B, Hq, hd) scaled; pool (num_blocks, page_size, Hkv, hd);
    lengths (B,) int32, 0 for rows that read nothing; page_table (B,
    max_pages).  Returns (B, Hq, hd)."""
    if interpret is None:
        interpret = not on_tpu()
    lengths = lengths.astype(jnp.int32)
    return paged_decode_attention(q, k_pages, v_pages, lengths,
                                  page_table.astype(jnp.int32),
                                  next_live_rows(lengths),
                                  interpret=interpret)


@jax.jit
def next_live_rows(lengths: jax.Array) -> jax.Array:
    """(B + 1,) int32: entry ``i`` the first row at or after ``i`` whose
    length is not 0, B where none is."""
    b = lengths.shape[0]
    rows = jnp.arange(b + 1, dtype=jnp.int32)
    live = jnp.where(jnp.append(lengths > 0, True), rows, b)
    return jax.lax.cummin(live, reverse=True)

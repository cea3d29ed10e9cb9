"""Pure-jnp oracle for the paged decode attention kernel: the gather path.

Each row's pages are gathered into a dense ``(B, max_pages * page_size,
Hkv, hd)`` view and attended in float32 with positions at or past the
row's length masked, the math of ``models.attention._decode_attention``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import NEG_INF


@jax.jit
def paged_decode_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, lengths: jax.Array,
                               page_table: jax.Array) -> jax.Array:
    """Reference for kernel.paged_decode_attention (same arguments but
    ``next_live``).  Idle rows (length 0) come out as the kernel's zeros."""
    b, hq, hd = q.shape
    hkv = k_pages.shape[2]
    k = k_pages[page_table].reshape(b, -1, hkv, hd)
    v = v_pages[page_table].reshape(b, -1, hkv, hd)
    qh = q.reshape(b, hkv, hq // hkv, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,btkh->bkgt", qh, k.astype(jnp.float32))
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    o = jnp.where((lengths > 0)[:, None, None, None], o, 0.0)
    return o.reshape(b, hq, hd).astype(q.dtype)

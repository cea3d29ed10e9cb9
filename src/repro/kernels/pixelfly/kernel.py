"""Pixelfly block-sparse matmul Pallas TPU kernel.

Flat block butterfly = block-sparse matmul whose support is pure XOR
structure: output block-row ``o`` reads input block-cols ``o`` and
``o ^ 2^i``.  That means **no gather tables**: the input block index is
computed inside the BlockSpec ``index_map`` from the grid position, so the
kernel streams exactly the log2(nb)+1 relevant (TM, b) input tiles per output
tile and accumulates in the revolving output block (standard Pallas K-loop
accumulation with the contraction axis innermost).  x and y keep their
plain (M, N) layout: a (TM, b) block of column-block ``o`` is a 2-D tile.

This is the TPU replacement for the paper's GPU/Triton block alignment: the
support blocks are already MXU-shaped, so "alignment" is free and the
sparsity shows up purely as a shorter K loop (k_blocks instead of nb).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.utils import ilog2
from repro.kernels.vmem import compiler_params


def _col_index(o, j):
    """Input block-col for output block-row o, support slot j (traced ints)."""
    # slot 0 -> diagonal; slot j>0 -> o ^ 2^(j-1)
    shift = jnp.maximum(j - 1, 0)
    mask = jnp.where(j == 0, 0, jnp.left_shift(1, shift))
    return jnp.bitwise_xor(o, mask)


def _bsmm_kernel(x_ref, w_ref, o_ref, acc):
    j = pl.program_id(2)
    k = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # (TM, b) tile of the needed input block-col @ (b, b) support block
    acc[...] += jnp.dot(x_ref[...].astype(w_ref.dtype), w_ref[0, 0],
                        preferred_element_type=jnp.float32)

    @pl.when(j == k - 1)
    def _store():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "batch_tile", "interpret")
)
def pixelfly_bsmm(
    x: jax.Array,
    w_blocks: jax.Array,
    *,
    block_size: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Block-sparse matmul with butterfly support.

    x: (M, N), N = nb * b; w_blocks: (nb, k, b, b) with k = 1 + log2(nb),
    w_blocks[o, j] maps input block col_index(o, j) to output block o.
    """
    m, n = x.shape
    nb, k = w_blocks.shape[0], w_blocks.shape[1]
    b = block_size
    assert nb * b == n
    assert k == 1 + ilog2(nb)
    assert m % batch_tile == 0

    need = 2 * (2 * batch_tile * b * x.dtype.itemsize
                + b * b * w_blocks.dtype.itemsize) + batch_tile * b * 4
    return pl.pallas_call(
        _bsmm_kernel,
        grid=(m // batch_tile, nb, k),
        in_specs=[
            pl.BlockSpec((batch_tile, b), lambda i, o, j: (i, _col_index(o, j))),
            pl.BlockSpec((1, 1, b, b), lambda i, o, j: (o, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((batch_tile, b), lambda i, o, j: (i, o)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((batch_tile, b), jnp.float32)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), need),
        interpret=interpret,
        name="pixelfly_bsmm",
    )(x, w_blocks)

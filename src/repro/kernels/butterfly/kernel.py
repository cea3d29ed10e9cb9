"""Fused block-butterfly Pallas TPU kernel.

The paper's IPU win comes from keeping the whole working set in on-chip SRAM.
The TPU analogue: keep the activation tile **VMEM-resident across all
log2(nb) butterfly factors** — one HBM read of x, one HBM write of y, and the
(tiny, O(N b log nb)) factor weights streamed factor-by-factor through the
grid pipeline.  The unfused jnp path instead round-trips (TM, N) activations
to HBM once per factor, i.e. ~log2(nb) x more HBM traffic.

Grid: (num_batch_tiles, L) with the factor axis innermost ("arbitrary"
semantics).  A VMEM scratch holds the activation tile between factor steps;
factor weights arrive packed as (L, nb, 2, b, b):

    w_packed[l, o, c] = W_l[j, r, c, t]   with  o = j*2s + r*s + t,  s = 2^l

so output block ``o`` of factor ``l`` is x_block(o & ~s) @ w[o, 0] +
x_block(o | s) @ w[o, 1].  Blocks ``a`` and ``a | s`` (bit s of ``a`` clear)
read and write only each other, so each factor step is a static loop over
those pairs, four 2-D ``(TM, b) @ (b, b)`` MXU dots per pair, written back
in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.utils import ilog2
from repro.kernels.vmem import compiler_params


def pack_factors(factors, num_blocks: int, block_size: int) -> jax.Array:
    """Stack per-stride factors (J,2,2,S,b,b) into (L, nb, 2, b, b)."""
    packed = []
    for w in factors:
        # (j, r, c, t, i, o) -> (j, r, t, c, i, o); row-major (j,r,t) == out block
        wt = jnp.transpose(w, (0, 1, 3, 2, 4, 5))
        packed.append(wt.reshape(num_blocks, 2, block_size, block_size))
    return jnp.stack(packed)


def fused_vmem_bytes(batch_tile: int, n: int, block_size: int,
                     x_bytes: int, w_bytes: int) -> int:
    """VMEM the fused kernel holds: the x and y tiles and one factor's
    weight slab, each double-buffered by the pipeline, the f32 activation
    scratch, and one pair's f32 products."""
    tiles = 2 * 2 * batch_tile * n * x_bytes
    weights = 2 * 2 * n * block_size * w_bytes
    scratch = batch_tile * n * 4
    pair = 4 * batch_tile * block_size * 4
    return tiles + weights + scratch + pair


def _fused_kernel(x_ref, w_ref, o_ref, scratch, *, num_factors: int,
                  block_size: int):
    l = pl.program_id(1)
    nb = x_ref.shape[1] // block_size
    b = block_size

    @pl.when(l == 0)
    def _load():
        scratch[...] = x_ref[...].astype(scratch.dtype)

    # One static branch per factor: the stride is a Python constant inside
    # each, so every block slice is static and lane-aligned.
    for lf in range(num_factors):
        @pl.when(l == lf)
        def _apply(lf=lf):
            s = 1 << lf
            for a in range(nb):
                if a & s:
                    continue
                c = a | s
                xa = scratch[:, a * b:(a + 1) * b].astype(w_ref.dtype)
                xc = scratch[:, c * b:(c + 1) * b].astype(w_ref.dtype)

                def mix(o):
                    return (jnp.dot(xa, w_ref[0, o, 0],
                                    preferred_element_type=jnp.float32)
                            + jnp.dot(xc, w_ref[0, o, 1],
                                      preferred_element_type=jnp.float32))

                ya, yc = mix(a), mix(c)
                scratch[:, a * b:(a + 1) * b] = ya
                scratch[:, c * b:(c + 1) * b] = yc

    @pl.when(l == num_factors - 1)
    def _store():
        o_ref[...] = scratch[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "batch_tile", "interpret")
)
def fused_butterfly_apply(
    x: jax.Array,
    w_packed: jax.Array,
    *,
    block_size: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, N) with N = nb * b, nb a power of two.  Returns (M, N).

    M must be a multiple of batch_tile (ops.py pads).
    """
    m, n = x.shape
    num_factors, nb = w_packed.shape[0], w_packed.shape[1]
    assert nb * block_size == n, (nb, block_size, n)
    assert m % batch_tile == 0, (m, batch_tile)
    assert 1 << ilog2(nb) == nb

    grid = (m // batch_tile, num_factors)
    kernel = functools.partial(
        _fused_kernel, num_factors=num_factors, block_size=block_size
    )
    need = fused_vmem_bytes(batch_tile, n, block_size, x.dtype.itemsize,
                            w_packed.dtype.itemsize)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((batch_tile, n), lambda i, l: (i, 0)),
            pl.BlockSpec(
                (1, nb, 2, block_size, block_size), lambda i, l: (l, 0, 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((batch_tile, n), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((batch_tile, n), jnp.float32)],
        compiler_params=compiler_params(("parallel", "arbitrary"), need),
        interpret=interpret,
        name="butterfly_fused",
    )(x, w_packed)


def _single_factor_kernel(x_ref, w_ref, o_ref, acc):
    """Unfused single-factor kernel: output block o accumulates its two
    inputs, one per step of the innermost grid axis (input slot c)."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[...].astype(w_ref.dtype), w_ref[0, 0, 0, 0],
                        preferred_element_type=jnp.float32)

    @pl.when(c == 1)
    def _store():
        o_ref[...] = acc[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("stride", "block_size", "batch_tile", "interpret")
)
def butterfly_factor_apply(
    x: jax.Array,
    w: jax.Array,
    *,
    stride: int,
    block_size: int,
    batch_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Apply ONE butterfly factor.  x: (M, N); w: (J, 2, 2, S, b, b).

    Output block o = j*2s + r*s + t reads input blocks j*2s + c*s + t for
    c in {0, 1} with weight w[j, r, c, t]; both block indices are computed
    in the index_maps, so x and y keep their plain (M, N) layout."""
    m, n = x.shape
    nb = n // block_size
    s = stride
    j = nb // (2 * s)
    assert w.shape == (j, 2, 2, s, block_size, block_size)
    assert m % batch_tile == 0

    def x_block(i, o, c):
        return i, (o // (2 * s)) * (2 * s) + c * s + o % s

    def w_block(i, o, c):
        return o // (2 * s), (o // s) % 2, c, o % s, 0, 0

    b = block_size
    need = 2 * (2 * batch_tile * b * x.dtype.itemsize
                + b * b * w.dtype.itemsize) + batch_tile * b * 4
    return pl.pallas_call(
        _single_factor_kernel,
        grid=(m // batch_tile, nb, 2),
        in_specs=[
            pl.BlockSpec((batch_tile, b), x_block),
            pl.BlockSpec((1, 1, 1, 1, b, b), w_block),
        ],
        out_specs=pl.BlockSpec((batch_tile, b), lambda i, o, c: (i, o)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((batch_tile, b), jnp.float32)],
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"), need),
        interpret=interpret,
        name="butterfly_factor",
    )(x, w)

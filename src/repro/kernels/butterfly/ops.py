"""jit'd public wrappers around the butterfly Pallas kernels.

On CPU (this container) the kernels run with ``interpret=True``; on TPU they
compile natively.  ``butterfly_linear`` is registered as the "butterfly"
kernel backend in the factorization registry (see repro/kernels/__init__.py);
``repro.core.Linear`` routes through it when the site's Rule sets
``use_kernel``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.butterfly import ButterflySpec
from repro.core.utils import bit_reversal_permutation
from repro.kernels.butterfly.kernel import (fused_butterfly_apply,
                                           fused_vmem_bytes, pack_factors)
from repro.kernels.vmem import LANES, TILE_BUDGET, VMEM_CAPACITY

import numpy as np


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pick_batch_tile(m: int, n: int, block_size: int, x_bytes: int,
                     w_bytes: int) -> int:
    """Largest TM whose double-buffered tiles, weight slab and f32 scratch
    fit the VMEM tile budget (else the smallest, for which the kernel
    raises its VMEM limit); M itself when one tile holds every row."""
    for tm in (512, 256, 128, 64, 32, 16):
        if fused_vmem_bytes(tm, n, block_size, x_bytes, w_bytes) \
                <= TILE_BUDGET:
            break
    return m if m <= tm else tm


def supports(spec: ButterflySpec) -> bool:
    """What the fused kernel compiles for on the chip: lane-aligned blocks
    (b a multiple of 128) whose smallest tile fits physical VMEM."""
    return (spec.block_size % LANES == 0
            and fused_vmem_bytes(16, spec.n_padded, spec.block_size, 4, 4)
            <= VMEM_CAPACITY)


def fused_apply(
    x: jax.Array,
    factors,
    *,
    block_size: int,
    interpret: bool | None = None,
    batch_tile: int | None = None,
) -> jax.Array:
    """Apply the full butterfly product to the last axis via the fused kernel.

    x: (..., N) with N = nb * block_size.  Handles batch flattening + padding.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n = x.shape[-1]
    nb = n // block_size
    w_packed = pack_factors(factors, nb, block_size)
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    xf = x.reshape(m, n)
    # decode fast path: inputs narrower than a tile (M = num_slots, e.g. 4)
    # take a single exact tile instead of padding up to 8 — no wasted rows
    tm = min(batch_tile, m) if batch_tile else _pick_batch_tile(
        m, n, block_size, x.dtype.itemsize, w_packed.dtype.itemsize)
    pad = (-m) % tm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    y = fused_butterfly_apply(
        xf, w_packed, block_size=block_size, batch_tile=tm, interpret=interpret
    )
    if pad:
        y = y[:m]
    return y.reshape(*lead, n)


def butterfly_linear(spec: ButterflySpec, params: dict, x: jax.Array) -> jax.Array:
    """Kernel-backed equivalent of ``ButterflySpec.apply``."""
    n = spec.n_padded
    pad = n - spec.in_features
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    if spec.permute == "bitrev":
        perm = np.asarray(bit_reversal_permutation(spec.num_blocks))
        xb = x.reshape(*x.shape[:-1], spec.num_blocks, spec.block_size)
        x = xb[..., perm, :].reshape(x.shape)
    y = fused_apply(x, params["factors"], block_size=spec.block_size)
    y = y[..., : spec.out_features]
    if spec.bias:
        y = y + params["bias"]
    return y

"""VMEM sizing shared by the Pallas kernels (TPU v5e figures).

Mosaic refuses a kernel whose blocks, double-buffered by the grid
pipeline, plus its scratch exceed the scoped VMEM limit.  The default
limit is 16 MiB on v5e; a kernel may raise it, up to the 128 MiB of
physical VMEM, through ``vmem_limit_bytes``.  Tiles are picked to fit
``TILE_BUDGET`` (room is left for Mosaic's own temporaries); a kernel
whose smallest tile still needs more asks for a higher limit.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

SCOPED_VMEM_DEFAULT = 16 * 2**20
VMEM_CAPACITY = 128 * 2**20
TILE_BUDGET = SCOPED_VMEM_DEFAULT * 3 // 4
LANES = 128


def compiler_params(dimension_semantics: tuple[str, ...],
                    need_bytes: int) -> pltpu.CompilerParams:
    """Compiler params that raise the scoped limit only when ``need_bytes``
    (the kernel's own count of its VMEM footprint) exceeds the budget."""
    limit = None
    if need_bytes > TILE_BUDGET:
        limit = min(VMEM_CAPACITY, need_bytes + SCOPED_VMEM_DEFAULT // 4)
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=limit)

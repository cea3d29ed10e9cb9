"""jit'd public wrappers around the pixelfly block-sparse kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pixelfly import PixelflySpec
from repro.kernels.pixelfly.kernel import pixelfly_bsmm
from repro.kernels.vmem import LANES


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def supports(spec: PixelflySpec) -> bool:
    """What the kernel compiles for on the chip: lane-aligned (TM, b)
    tiles, i.e. b a multiple of 128."""
    return spec.block_size % LANES == 0


def bsmm(
    x: jax.Array,
    w_blocks: jax.Array,
    *,
    block_size: int,
    interpret: bool | None = None,
    batch_tile: int = 128,
) -> jax.Array:
    """Batched flat-butterfly matmul over the last axis of x."""
    if interpret is None:
        interpret = not _on_tpu()
    n = x.shape[-1]
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    xf = x.reshape(m, n)
    # one exact tile when every row fits (decode: M = num_slots)
    tm = min(batch_tile, m)
    pad = (-m) % tm
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    y = pixelfly_bsmm(
        xf, w_blocks, block_size=block_size, batch_tile=tm, interpret=interpret
    )
    if pad:
        y = y[:m]
    return y.reshape(*lead, n)


def pixelfly_linear(spec: PixelflySpec, params: dict, x: jax.Array) -> jax.Array:
    """Kernel-backed equivalent of ``PixelflySpec.apply``."""
    n = spec.n_padded
    pad = n - spec.in_features
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    y = bsmm(xp, params["blocks"], block_size=spec.block_size)
    y = y[..., : spec.out_features]
    if spec.rank > 0:
        y = y + (x @ params["u"]) @ params["v"]
    if spec.bias:
        y = y + params["bias"]
    return y

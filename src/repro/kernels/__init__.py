"""Pallas kernel backends, attached to the factorization registry.

Importing this package registers the fused butterfly and block-sparse
pixelfly kernels as the accelerator backends for their kinds — the core
layer (``repro.core.factorized.Linear``) never imports kernel modules; it
calls ``registry.ensure_kernels_registered()`` which imports us.  Each
backend's ``supports`` predicate says exactly which specs its kernel
compiles for on the chip (lane-aligned blocks, b a multiple of 128); every
other spec takes the jnp reference path, on every platform.

The raw pallas_calls have no JVP rule, so each backend is wrapped in a
custom VJP: kernel forward, reference-``spec.apply`` backward.  The two
paths agree within kernel tolerance (asserted by the kernel test suite),
so training with ``use_kernel`` rules is exact up to that tolerance
instead of crashing in ``jax.grad``.
"""
import jax

from repro.core.registry import register_kernel
from repro.kernels.butterfly import ops as butterfly_ops
from repro.kernels.pixelfly import ops as pixelfly_ops


def _differentiable(kernel_fn):
    """Kernel forward + reference backward (the spec's jnp apply)."""
    def apply(spec, params, x):
        @jax.custom_vjp
        def f(params, x):
            return kernel_fn(spec, params, x)

        def fwd(params, x):
            return f(params, x), (params, x)

        def bwd(res, g):
            _, vjp = jax.vjp(spec.apply, *res)
            return vjp(g)

        f.defvjp(fwd, bwd)
        return f(params, x)
    return apply


register_kernel("butterfly", _differentiable(butterfly_ops.butterfly_linear),
                supports=butterfly_ops.supports)
register_kernel("pixelfly", _differentiable(pixelfly_ops.pixelfly_linear),
                supports=pixelfly_ops.supports)

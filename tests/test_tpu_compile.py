"""The Pallas kernels compile for a described TPU v5e (no chip attached).

Interpret mode accepts block shapes and VMEM footprints that the chip's
compiler refuses, so these tests compile each kernel with the TPU compiler
at the factorized qwen3-4b site shapes: butterfly (fused, and one factor
alone) N = 4096 and pixelfly N = 16384, b = 128, at decode (M = 4 slots)
and prefill (M = 512) rows.
The topology is described inside a fixture: only the worker that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.butterfly import ButterflySpec
from repro.core.pixelfly import PixelflySpec
from repro.kernels.butterfly import ops as butterfly_ops
from repro.kernels.butterfly.kernel import (butterfly_factor_apply,
                                           fused_butterfly_apply)
from repro.kernels.pixelfly import ops as pixelfly_ops
from repro.kernels.pixelfly.kernel import pixelfly_bsmm

B = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for an absent chip is written to the persistent cache but
    # cannot be read back; keep the cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)


@pytest.mark.parametrize("m", [4, 512])
def test_fused_butterfly_compiles_for_v5e(one_chip, m):
    n = 4096
    nb = n // B
    tm = butterfly_ops._pick_batch_tile(m, n, B, 2, 2)
    fn = jax.jit(lambda x, w: fused_butterfly_apply(
        x, w, block_size=B, batch_tile=tm))
    compiled = fn.lower(_shape(one_chip, (m, n)),
                        _shape(one_chip, (nb.bit_length() - 1, nb, 2, B, B))
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m,stride", [(4, 1), (512, 16)])
def test_single_factor_butterfly_compiles_for_v5e(one_chip, m, stride):
    n = 4096
    j = n // B // (2 * stride)
    fn = jax.jit(lambda x, w: butterfly_factor_apply(
        x, w, stride=stride, block_size=B, batch_tile=min(128, m)))
    compiled = fn.lower(_shape(one_chip, (m, n)),
                        _shape(one_chip, (j, 2, 2, stride, B, B))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [4, 512])
def test_pixelfly_compiles_for_v5e(one_chip, m):
    n = 16384
    nb = n // B
    fn = jax.jit(lambda x, w: pixelfly_bsmm(
        x, w, block_size=B, batch_tile=min(128, m)))
    compiled = fn.lower(_shape(one_chip, (m, n)),
                        _shape(one_chip, (nb, nb.bit_length(), B, B))
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_supports_says_what_compiles():
    """b = 128 is lane-aligned and takes the kernels; butterfly-lm-100m's
    b = 16 is not, so the predicate sends it to the jnp path."""
    assert butterfly_ops.supports(ButterflySpec(2560, 4096, block_size=128))
    assert pixelfly_ops.supports(PixelflySpec(2560, 9728, block_size=128))
    assert not butterfly_ops.supports(ButterflySpec(768, 768, block_size=16))
    assert not pixelfly_ops.supports(PixelflySpec(768, 3072, block_size=16))


def test_unaligned_block_is_refused_for_v5e(one_chip):
    """The shape the predicate rejects really does not compile: a (TM, 16)
    pixelfly tile breaks the (8, 128) rule for the last block dims."""
    n, b = 256, 16
    nb = n // b
    fn = jax.jit(lambda x, w: pixelfly_bsmm(x, w, block_size=b,
                                            batch_tile=16))
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        fn.lower(_shape(one_chip, (32, n)),
                 _shape(one_chip, (nb, nb.bit_length(), b, b))).compile()

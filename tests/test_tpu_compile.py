"""The Pallas kernels compile for a described TPU v5e (no chip attached).

Interpret mode accepts block shapes and VMEM footprints that the chip's
compiler refuses, so these tests compile each kernel with the TPU compiler
at the factorized qwen3-4b site shapes: butterfly (fused, and one factor
alone) N = 4096 and pixelfly N = 16384, b = 128, at decode (M = 4 slots)
and prefill (M = 512) rows; and the paged decode attention kernel at the
chat cell's pool (32 rows, 96 pages of 16, 1485 blocks, 32 q heads and 8
kv heads of 128), alone and inside the decode step.
The topology is described inside a fixture: only the worker that runs this
file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen3_4b import FACTORIZED_CONFIG
from repro.core.butterfly import ButterflySpec
from repro.core.pixelfly import PixelflySpec
from repro.kernels.butterfly import ops as butterfly_ops
from repro.kernels.butterfly.kernel import (butterfly_factor_apply,
                                           fused_butterfly_apply)
from repro.kernels.pixelfly import ops as pixelfly_ops
from repro.kernels.paged_attention import ops as paged_ops
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.kernels.pixelfly.kernel import pixelfly_bsmm
from repro.models import decode_step, init_params
from repro.models.model import cast_params, init_paged_caches

B = 128
# the chat cell's decode step: slots, pages per row, page size, pool blocks
ROWS, MAX_PAGES, PAGE, BLOCKS = 32, 96, 16, 1485


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


def test_paged_attention_compiles_for_v5e(one_chip):
    """The kernel compiles at the cell's pool, and the pool reaches it as
    its own bytes (a bitcast to page rows, no copy)."""
    fn = jax.jit(paged_decode_attention)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=one_chip)
    pool = _shape(one_chip, (BLOCKS, PAGE, 8, 128))
    text = fn.lower(_shape(one_chip, (ROWS, 32, 128)), pool, pool, i32(ROWS),
                    i32(ROWS, MAX_PAGES), i32(ROWS + 1)).compile().as_text()
    assert "tpu_custom_call" in text
    made = re.findall(rf"= bf16\[{BLOCKS},[^\]]*\]\S* ([\w-]+)\(", text)
    assert set(made) <= {"parameter", "bitcast"}, made


def test_decode_step_takes_paged_kernel_for_v5e(one_chip, monkeypatch):
    """The factorized qwen3-4b decode step, at the cell's widths and pool
    (two layers: the layer scan compiles one body whatever the depth),
    compiled for the chip: its attention is the kernel's custom call under
    ``attn.core``, which the benchmark's scope readers credit, and no op of
    the paged gather is left."""
    monkeypatch.setattr(paged_ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(FACTORIZED_CONFIG, num_layers=2)

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda k: cast_params(init_params(cfg, k), cfg.dtype),
        jax.random.PRNGKey(0)))
    caches = placed(jax.eval_shape(
        lambda: init_paged_caches(cfg, ROWS, BLOCKS, PAGE)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=one_chip)
    step = jax.jit(lambda p, c, table, tok, pos: decode_step(
        p, cfg, tok, c, pos, page_table=table, page_size=PAGE,
        kv_len=MAX_PAGES * PAGE))
    text = step.lower(params, caches, i32(ROWS, MAX_PAGES), i32(ROWS, 1),
                      i32(ROWS)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "paged_decode_attention" in line]
    assert calls
    for line in calls:
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/attn.core/" in op_name, op_name
    assert "attn.kv_gather" not in text

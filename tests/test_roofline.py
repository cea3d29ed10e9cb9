"""Roofline machinery: HLO cost walker (trip counts, dots, collectives)."""
import jax
import jax.numpy as jnp
import numpy as np

import pytest

from repro.roofline.analysis import (
    CollectiveStats,
    Roofline,
    parse_collectives,
    peaks_for,
)
from repro.roofline.hlo_cost import hlo_cost, parse_module

HLO_EXAMPLE = """
HloModule test, num_partitions=8

%body (p: (s32[], f32[8,16], f32[64,16])) -> (s32[], f32[8,16], f32[64,16]) {
  %p = (s32[], f32[8,16]{1,0}, f32[64,16]{1,0}) parameter(0)
  %gte0 = s32[] get-tuple-element(%p), index=0
  %gte1 = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %gte2 = f32[64,16]{1,0} get-tuple-element(%p), index=2
  %c1 = s32[] constant(1)
  %add1 = s32[] add(%gte0, %c1)
  %ag = f32[8,64]{1,0} all-gather(%gte1), channel_id=1, replica_groups=[2,4]<=[8], dimensions={1}
  %dot1 = f32[8,16]{1,0} dot(%ag, %gte2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,16]{1,0}, f32[64,16]{1,0}) tuple(%add1, %dot1, %gte2)
}

%cond (p2: (s32[], f32[8,16], f32[64,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]{1,0}, f32[64,16]{1,0}) parameter(0)
  %g = s32[] get-tuple-element(%p2), index=0
  %n = s32[] constant(7)
  ROOT %cmp = pred[] compare(%g, %n), direction=LT
}

ENTRY %main (a: f32[8,16], w: f32[64,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %w = f32[64,16]{1,0} parameter(1)
  %c0 = s32[] constant(0)
  %tup = (s32[], f32[8,16]{1,0}, f32[64,16]{1,0}) tuple(%c0, %a, %w)
  %loop = (s32[], f32[8,16]{1,0}, f32[64,16]{1,0}) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  %res = f32[8,16]{1,0} get-tuple-element(%loop), index=1
  ROOT %ar = f32[8,16]{1,0} all-reduce(%res), channel_id=2, replica_groups=[2,4]<=[8]
}
"""


def test_walker_trip_count_multiplies_dots():
    c = hlo_cost(HLO_EXAMPLE)
    # 7 iterations x dot(8x64 @ 64x16) = 7 * 2*8*16*64
    assert c.dot_flops == 7 * 2 * 8 * 16 * 64


def test_walker_collectives_trip_aware():
    c = hlo_cost(HLO_EXAMPLE)
    assert c.coll_bytes["all-gather"] == 7 * 8 * 16 * 4  # operand f32[8,16]
    assert c.coll_bytes["all-reduce"] == 8 * 16 * 4
    assert c.coll_counts["all-gather"] == 7


def test_walker_matches_real_compile():
    """End-to-end: scan of matmuls, exact expected flops."""
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y.sum()

    comp = jax.jit(f).lower(
        jax.ShapeDtypeStruct((4, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
    c = hlo_cost(comp.as_text())
    assert c.dot_flops == 5 * 2 * 4 * 32 * 32


def test_parse_module_finds_computations():
    comps = parse_module(HLO_EXAMPLE)
    assert "__entry__" in comps and "body" in comps and "cond" in comps
    assert any(i.opcode == "while" for i in comps["__entry__"])


def test_roofline_terms_and_dominance():
    r = Roofline(peaks=peaks_for("TPU v5 lite"),
                 dot_flops=197e12, ew_flops=0.0, dot_bytes=819e9 / 2,
                 buffer_bytes=0.0, collective_bytes_per_device=0.0,
                 collective_breakdown={}, collective_counts={})
    np.testing.assert_allclose(r.compute_s, 1.0)
    np.testing.assert_allclose(r.memory_s, 0.5)
    assert r.dominant == "compute"
    assert r.compute_fraction == 1.0
    d = r.to_dict()
    assert d["dominant"] == "compute"


def test_unknown_device_kind_raises():
    """A device with no entry in the peak table is an error, not v5e."""
    with pytest.raises(KeyError, match="no peak rates"):
        peaks_for("TPU v4")


def test_legacy_collective_parser():
    stats = parse_collectives(HLO_EXAMPLE)
    # trip-UNaware (kept for comparison): all-gather counted once
    assert stats.count_by_kind["all-gather"] == 1
    assert isinstance(stats, CollectiveStats)

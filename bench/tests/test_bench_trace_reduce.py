"""The trace reduction on a small synthetic trace."""
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def op(name, start, dur, plane=DEV0):
    return Event(plane, tr.OPS_LINE, name, start, dur)


def module(name, start, dur, plane=DEV0):
    return Event(plane, tr.MODULES_LINE, name, start, dur)


def span(name, start, dur):
    return Event(HOST, "python", name, start, dur)


@pytest.fixture
def events():
    return [
        # decode step 0..100: two ops overlapping, one collective
        module("jit_step_fn(7)", 0, 100),
        op("fusion.1", 0, 60), op("fusion.2", 50, 30),
        op("all-gather.3", 80, 20),
        # idle 100..150, then a prefill 150..250
        module("jit_prefill_fn(9)", 150, 100),
        op("fusion.4", 150, 100),
        # idle 250..300
        op("fusion.5", 0, 300, plane=DEV1),
        span("bench.step", 0, 105), span("bench.idle", 105, 40),
        span("bench.step", 145, 110), span("bench.submit", 255, 45),
    ]


def test_busy_union_counts_overlap_once(events):
    assert tr.busy_ns(events, DEV0) == 200
    assert tr.busy_ns(events, DEV0, 50, 160) == 60
    assert tr.busy_ns(events, DEV1) == 300


def test_idle_gaps_and_share(events):
    gaps = tr.idle_gaps(events, DEV0, 0, 300)
    assert gaps == [(100, 150), (250, 300)]
    idle = sum(b - a for a, b in gaps) / 300
    assert idle == pytest.approx(1 - tr.busy_ns(events, DEV0, 0, 300) / 300)


def test_gap_attribution_names_the_overlapping_span(events):
    gaps = tr.idle_gaps(events, DEV0, 0, 300)
    out = tr.attribute_gaps(gaps, tr.host_spans(events))
    assert out == [["bench.idle", 50e-9], ["bench.submit", 50e-9]]


def test_op_grouping_by_name(events):
    assert tr.op_group("fusion.12") == "fusion"
    assert tr.op_group("all-gather.3") == "all-gather"
    assert tr.op_group("%fusion.374 = bf16[8]{0} fusion(bf16[8]{0} %p)") \
        == "fusion"
    top = dict(tr.top_ops(events, DEV0))
    assert top["jit_step_fn/fusion"] == pytest.approx(90e-9)
    assert top["jit_step_fn/all-gather"] == pytest.approx(20e-9)
    assert top["jit_prefill_fn/fusion"] == pytest.approx(100e-9)
    # a loop's body is traced inside the loop: each op counts its own time
    nested = events + [
        module("jit_step_fn(7)", 400, 100),
        op("%while.8 = (s32[]) while((s32[]) %t)", 400, 100),
        op("%fusion.9 = bf16[4]{0} fusion(bf16[4]{0} %a)", 410, 30),
        op("%fusion.10 = bf16[4]{0} fusion(bf16[4]{0} %b)", 450, 20)]
    top = dict(tr.top_ops(nested, DEV0))
    assert top["jit_step_fn/while"] == pytest.approx(50e-9)
    assert top["jit_step_fn/fusion"] == pytest.approx(140e-9)
    assert sum(top.values()) == pytest.approx(
        tr.busy_ns(nested, DEV0) / 1e9 + 10e-9)  # fusion.1/.2 overlap 10


def test_module_events_and_collective_share(events):
    mods = tr.module_events(events, DEV0, "jit_step_fn")
    assert [m.name for m in mods] == ["jit_step_fn(7)"]
    ops = tr.ops_within(events, DEV0, mods)
    assert {o.name for o in ops} == {"fusion.1", "fusion.2", "all-gather.3"}
    coll = sum(o.dur_ns for o in ops if tr.is_collective(o.name))
    assert coll / sum(m.dur_ns for m in mods) == pytest.approx(0.2)
    assert tr.module_events(events, DEV0, "jit_step") == []


def test_device_planes_sorted_by_index(events):
    assert tr.device_planes(events + [op("x", 0, 1, "/device:TPU:10")]) == [
        DEV0, DEV1, "/device:TPU:10"]

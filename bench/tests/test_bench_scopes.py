"""The readers of the program's spans and scopes on a small synthetic trace:
each against a sum by hand, None where the program under test has none,
and the accepted readers unmoved by the program's spans."""
import pytest

from bench import harness, scopes, trace_reduce as tr
from bench.peaks import peaks_for
from bench.tests.conftest import tiny_config
from bench.trace_reduce import Event

DEV0, HOST = "/device:TPU:0", "/host:CPU"
NEW = ("decode_fact_ms", "decode_attn_ms", "step_host_ms",
       "prefill_stall_ms")
TRACE_READERS = ("decode_step_ms", "prefill_ms_per_ktok",
                 "decode_collective_pct", "decode_hbm_roofline",
                 "step_mfu_pct", "device_idle_pct")

HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,16], param_1: bf16[16,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  %param_1 = bf16[16,16]{1,0:T(8,128)(2,1)} parameter(1)
  %convolution.1 = bf16[8,16]{1,0} convolution(bf16[8,16]{1,0} %param_0, bf16[16,16]{1,0} %param_1), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/layers/while/body/fact.butterfly.attn_qkv/dot_general"}
  ROOT %add.1 = bf16[8,16]{1,0} add(%convolution.1, %param_0), metadata={op_name="jit(step_fn)/layers/while/body/add"}
}

%fused_computation.2 (param_0.1: f32[8,16]) -> f32[8] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %exponential.1 = f32[8,16]{1,0} exponential(%param_0.1), metadata={op_name="jit(step_fn)/attn.core/exp"}
  %multiply.1 = f32[8,16]{1,0} multiply(%exponential.1, %param_0.1), metadata={op_name="jit(step_fn)/attn.core/mul"}
  %constant.1 = f32[] constant(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%multiply.1, %constant.1), dimensions={1}, to_apply=%region_0, metadata={op_name="jit(step_fn)/reduce_sum"}
}

%body.1 (p: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %p = (s32[], bf16[8,16]{1,0}) parameter(0)
  %convolution.9 = bf16[8,16]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, metadata={op_name="jit(step_fn)/layers/while/body/dense.head/dot_general"}
}

ENTRY %main.9 (p0: bf16[8,16], p1: bf16[16,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %fusion.1 = (bf16[8]{0:T(256)S(1)}, bf16[8,16]{1,0}) fusion(bf16[8,16]{1,0} %p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step_fn)/layers/while/body/add"}
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step_fn)/reduce_sum"}
  %copy.3 = bf16[8,16]{1,0} copy(%p0), metadata={op_name="jit(step_fn)/attn.kv_write/scatter"}
  %while.4 = (s32[], bf16[8,16]{1,0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(step_fn)/layers/while"}
  ROOT %tuple.5 = (bf16[8,16]{1,0}) tuple(%copy.3)
}
"""


def test_scope_of_takes_the_innermost_scope():
    assert scopes.scope_of("jit(step_fn)/layers/while/body/"
                           "fact.pixelfly.mlp/dot_general") \
        == "fact.pixelfly.mlp"
    assert scopes.scope_of("jit(step_fn)/layers/while/body/add") == "layers"
    assert scopes.scope_of("jit(step_fn)/sample/argmax") == "sample"
    assert scopes.scope_of("jit(step_fn)/reduce_sum") is None


def test_op_scopes_credit_fusions_to_their_matmul_or_their_body():
    got = scopes.op_scopes(HLO)
    # a fusion holding a matmul is the matmul's, whatever its root
    assert got["fusion.1"] == "fact.butterfly.attn_qkv"
    # an unscoped root falls back to the scope most fused ops carry
    assert got["fusion.2"] == "attn.core"
    assert got["copy.3"] == "attn.kv_write"
    # a loop's body is not the loop's own work
    assert got["while.4"] == "layers"
    assert "tuple.5" not in got


def _op(name, start, dur):
    return Event(DEV0, tr.OPS_LINE, f"%{name} = bf16[8]{{0}} op()", start,
                 dur)


def _span(name, start, dur):
    return Event(HOST, "python", name, start, dur)


def _device_events():
    """Two decode executions and one prefill; ns."""
    return [
        Event(DEV0, tr.MODULES_LINE, "jit_step_fn(7)", 0, 100),
        _op("fusion.1", 0, 40), _op("fusion.2", 40, 30),
        _op("copy.3", 70, 20), _op("tuple.5", 90, 10),
        Event(DEV0, tr.MODULES_LINE, "jit_step_fn(7)", 1000, 100),
        _op("fusion.1", 1000, 50), _op("copy.3", 1050, 10),
        # the loop holds a matmul op; each counts its own time
        _op("while.4", 1060, 40), _op("fusion.2", 1070, 20),
        Event(DEV0, tr.MODULES_LINE, "jit_prefill_fn(9)", 2000, 500),
        _op("fusion.1", 2000, 500),
    ]


def _host_events():
    """Two decode steps and one prefill step, each inside a bench.step."""
    return [
        _span("bench.step", 0, 1000), _span("serve.step", 5, 990),
        _span("serve.schedule", 10, 5), _span("serve.stage.decode", 20, 30),
        _span("serve.launch.decode", 50, 50),
        _span("serve.wait.decode", 100, 800), _span("serve.emit", 900, 50),
        _span("bench.step", 1000, 1000), _span("serve.step", 1002, 998),
        _span("serve.wait.decode", 1100, 700),
        _span("bench.step", 2000, 1000), _span("serve.step", 2000, 1000),
        _span("serve.wait.prefill", 2050, 500),
        _span("serve.cache.insert", 2600, 300),
    ]


def _run(events, stats=None):
    cfg = tiny_config()
    steps = [harness.Step(0, 1, [10, 20], [], None, None),
             harness.Step(1, 2, [11, 21], [], None, None),
             harness.Step(2, 3, [], [16, 8], None, None)]
    spans = tr.host_spans(events)   # the benchmark's own, as reduce_trace
    t0 = min(e.start_ns for e in spans)
    t1 = max(e.end_ns for e in spans)
    busy = tr.busy_ns(events, DEV0, t0, t1) / 1e9
    trace = {"events": events, "plane": DEV0, "planes": [DEV0],
             "span": (0, 3), "window_s": (t1 - t0) / 1e9, "busy_s": busy,
             "busy0_s": busy}
    return harness.Run(cfg, {}, 1, 1, peaks_for("TPU v5 lite"), 0.0, (0, 3),
                       [], steps, stats or {}, 0, trace=trace)


@pytest.fixture
def published(monkeypatch):
    monkeypatch.setattr(scopes, "program_scopes",
                        lambda module: scopes.op_scopes(HLO)
                        if module == "jit_step_fn" else None)


def test_device_readers_against_hand_sums(published):
    run = _run(_device_events() + _host_events())
    # fact: fusion.1 (40 + 50); attn: fusion.2 (30 + 20) and copy.3 (20
    # + 10); per execution of jit_step_fn, in ms
    assert harness.reader("decode_fact_ms")(run) == pytest.approx(45e-6)
    assert harness.reader("decode_attn_ms")(run) == pytest.approx(40e-6)
    times, n = scopes.scope_times(run.trace["events"], DEV0, "jit_step_fn",
                                  scopes.op_scopes(HLO))
    assert n == 2 and times[None] == 10           # tuple.5
    assert times["layers"] == pytest.approx(20)   # while.4 less fusion.2


def test_step_host_ms_against_hand_sum():
    run = _run(_device_events() + _host_events())
    # decode steps only: (990 - 800) and (998 - 700) ns
    assert harness.reader("step_host_ms")(run) == pytest.approx(244e-6)


def test_prefill_stall_ms_per_wave():
    read = harness.reader("prefill_stall_ms")
    events = _device_events() + _host_events()
    run = _run(events, {"prefill_stall_time": 0.8, "prefill_stall_waves": 4})
    assert read(run) == pytest.approx(200.0)
    assert read(_run(events, {"prefill_stall_time": 0.0,
                              "prefill_stall_waves": 0})) is None


def test_new_readers_read_nothing_from_a_program_without_them(monkeypatch):
    """A tree before spans and scopes: no serve.* spans, no published
    program, no stall counters."""
    monkeypatch.setattr(scopes, "program_scopes", lambda module: None)
    bench_only = [e for e in _host_events() if e.name.startswith("bench.")]
    run = _run(_device_events() + bench_only)
    for name in NEW:
        assert harness.reader(name)(run) is None, name


def test_program_scopes_reads_what_runners_published(monkeypatch):
    from repro.serving import tracing
    monkeypatch.setattr(tracing, "_programs", {})
    assert scopes.program_scopes("jit_step_fn") is None

    class Runner:
        ran = False

        def text(self):
            return HLO if self.ran else None

    r = Runner()
    tracing.publish("jit_step_fn", r.text)
    assert scopes.program_scopes("jit_step_fn") is None   # not run yet
    r.ran = True
    assert scopes.program_scopes("jit_step_fn")["fusion.2"] == "attn.core"
    del r   # held weakly: a dropped runner offers nothing
    assert scopes.program_scopes("jit_step_fn") is None


def test_accepted_readers_unmoved_by_the_program_spans(published):
    bench_only = [e for e in _host_events() if e.name.startswith("bench.")]
    without = _run(_device_events() + bench_only)
    with_spans = _run(_device_events() + _host_events())
    seen = 0
    for name in TRACE_READERS:
        a = harness.reader(name)(without)
        assert a == harness.reader(name)(with_spans), name
        seen += a is not None
    assert seen >= 4
    # the accepted gap attribution names only the benchmark's spans
    gaps = tr.idle_gaps(with_spans.trace["events"], DEV0, 0, 3000)
    assert tr.attribute_gaps(gaps, tr.host_spans(with_spans.trace["events"])) \
        == tr.attribute_gaps(gaps, tr.host_spans(without.trace["events"]))


def test_gaps_go_to_the_span_innermost_over_most_of_them():
    spans = _host_events()
    # 100..900 lies wholly in serve.wait.decode (inside serve.step and
    # bench.step); of 920..1000, serve.emit is innermost for 30 ns,
    # serve.step for 45 and bench.step for 5; of 955..1040, the two
    # serve.steps for 40 + 38 and the two bench.steps for 5 + 2
    gaps = [(100, 900), (920, 1000), (2600, 2900), (3100, 3200),
            (955, 1040)]
    got = scopes.attribute_gaps(gaps, spans)
    assert got == [["serve.wait.decode", 800e-9],
                   ["serve.cache.insert", 300e-9],
                   ["unattributed", 100e-9],
                   ["serve.step", 85e-9],
                   ["serve.step", 80e-9]]
    assert scopes.attribute_gaps(gaps, spans, n=2) == got[:2]

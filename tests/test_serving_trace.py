"""Spans and named scopes of the serving program (DESIGN.md section 14).

A tiny factorized paged engine is driven under ``jax.profiler``: the host
plane must hold the span vocabulary, every dispatch and cache span must
nest in a ``serve.step``, the steps must count up, and the runner's
dispatch timers must be its launch and wait spans.  The compiled
programs keep the names the benchmark's readers match, and the decode
program's op metadata carries the device scopes the trace is split by.
"""
import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, reduced
from repro.core.factorized import Linear
from repro.core.policy import FactorizationPolicy
from repro.models import init_params
from repro.serving import Engine, Request, make_requests, tracing

POLICY = {"default": {"kind": "dense"},
          "overrides": {"mlp": {"kind": "pixelfly", "block_size": 8,
                                "rank": 4},
                        "attn_*": {"kind": "butterfly", "block_size": 8}}}
DISPATCH = ("serve.stage.", "serve.launch.", "serve.wait.", "serve.cache.")


@pytest.fixture(scope="module")
def fact_setup():
    cfg = dataclasses.replace(reduced(get_config("qwen3-4b")),
                              fact=FactorizationPolicy.from_dict(POLICY))
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _engine(fact_setup):
    cfg, params = fact_setup
    return Engine(params, cfg, max_len=32, num_slots=4, page_size=4)


def _spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of every serve.* host event."""
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _drive(engine, rng, cfg):
    """Two waves, the second arriving while the first decodes."""
    engine.submit(Request("a", tuple(int(t) for t in
                                     rng.integers(0, cfg.vocab_size, 5)),
                          max_new=6))
    engine.step()   # prefill a
    engine.step()   # decode a
    engine.submit(Request("b", tuple(int(t) for t in
                                     rng.integers(0, cfg.vocab_size, 9)),
                          max_new=4))
    while engine.scheduler.has_work:
        engine.step()


def test_spans_nest_in_steps_that_count_up(fact_setup, tmp_path):
    cfg, _ = fact_setup
    engine = _engine(fact_setup)
    rng = np.random.default_rng(0)
    _drive(engine, rng, cfg)   # compile outside the trace
    st = engine.stats
    before = (st.decode_time, st.prefill_time)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.submit(Request("c", (1, 2, 3, 4, 5, 6, 7), max_new=3))
        _drive(engine, np.random.default_rng(1), cfg)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert {"serve.step", "serve.schedule", "serve.emit",
            "serve.stage.decode", "serve.launch.decode", "serve.wait.decode",
            "serve.stage.prefill", "serve.launch.prefill",
            "serve.wait.prefill", "serve.cache.insert",
            "serve.cache.ensure_mapped", "serve.cache.evict"} <= names
    steps = [s for s in spans if s[0] == "serve.step"]
    for name, t0, t1, _ in spans:
        if name.startswith(DISPATCH):
            assert any(s[1] <= t0 and t1 <= s[2] for s in steps), name
    index = [s[3]["step"] for s in steps]
    assert index == sorted(index) and len(set(index)) == len(index)
    kinds = {s[3]["kind"] for s in steps}
    assert {tracing.KIND_DECODE, tracing.KIND_PREFILL} <= kinds
    # a wave's schedule span carries its admit_seqno range
    waves = [s[3] for s in spans if s[0] == "serve.schedule" and s[3]]
    assert waves and all(w["first"] <= w["last"] for w in waves)
    # the dispatch timers are the launch and wait spans: finished work
    for kind, t0 in zip(("decode", "prefill"), before):
        timed = getattr(st, f"{kind}_time") - t0
        spanned = sum(t1 - t for name, t, t1, _ in spans if name in (
            f"serve.launch.{kind}", f"serve.wait.{kind}")) / 1e9
        assert timed == pytest.approx(spanned, rel=0.1, abs=1e-3), kind


def test_spans_record_nothing_with_the_profiler_off(fact_setup, tmp_path):
    """Off the profiler a span only times itself: nothing reaches a trace
    started afterwards, and a span still measures its stretch."""
    cfg, _ = fact_setup
    engine = _engine(fact_setup)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _drive(engine, np.random.default_rng(2), cfg)
    with tracing.span("serve.step", step=1) as sp:
        sp.note(kind=tracing.KIND_DECODE, rows=2)
    assert sp.seconds > 0
    jax.profiler.start_trace(str(tmp_path))
    jax.profiler.stop_trace()
    assert _spans(str(tmp_path)) == []


def test_prefill_stall_counts_waves_that_held_decode(fact_setup):
    """Only the second wave ran while a sequence was decode-ready."""
    cfg, _ = fact_setup
    engine = _engine(fact_setup)
    _drive(engine, np.random.default_rng(3), cfg)
    st = engine.stats
    assert st.prefill_stall_waves == 1
    assert 0 < st.prefill_stall_time < st.prefill_time + st.host_time


def test_compiled_programs_keep_the_names_readers_match(fact_setup):
    """The benchmark matches jit_step_fn, jit_prefill_fn, jit_prefix_fn."""
    engine = _engine(fact_setup)
    runner = engine.executor.runner
    z = np.zeros
    step = runner._step.lower(runner.params, runner.cache.data,
                              *runner._decode_staging())
    prefill = runner._prefill.lower(
        runner.params, z((1, 8), np.int32), z((1,), np.int32),
        z((1,), np.float32), z((1,), np.int32), z((1,), np.uint32),
        ragged=False)
    prefix = runner._prefix_prefill.lower(
        runner.params, runner.cache.data, z((1, 1), np.int32),
        z((1, 4), np.int32), z((1,), np.int32), z((1,), np.int32),
        z((1,), np.float32), z((1,), np.int32), z((1,), np.uint32))
    for lowered, name in ((step, "jit_step_fn"), (prefill, "jit_prefill_fn"),
                          (prefix, "jit_prefix_fn")):
        assert lowered.as_text().startswith(f"module @{name} ")


def test_decode_program_carries_the_device_scopes(fact_setup):
    engine = _engine(fact_setup)
    runner = engine.executor.runner
    text = runner._step.lower(runner.params, runner.cache.data,
                              *runner._decode_staging()).as_text(
                                  debug_info=True)
    # the location names hold each op's scope path
    parts = {p for loc in re.findall(r'loc\("([^"]*)"', text)
             for p in loc.split("/")}
    for scope in ("attn.kv_gather", "attn.kv_write", "attn.core",
                  "attn.rope", "fact.butterfly.attn_qkv",
                  "fact.butterfly.attn_out", "fact.pixelfly.mlp",
                  "dense.head", "sample", "layers", "norm", "embed"):
        assert scope in parts, scope
    # the compiled text is offered to trace reductions once it has run
    assert runner.decode_program_text() is None
    engine.run(make_requests([[1, 2, 3]], max_new=2))
    compiled = runner.decode_program_text()
    assert compiled.startswith("HloModule jit_step_fn")
    assert any(t is compiled or t == compiled
               for t in tracing.program_texts("jit_step_fn"))


def test_linear_scope_names_kind_and_site():
    fact = FactorizationPolicy.from_dict(POLICY)
    assert Linear(fact, 16, 16, site="attn_qkv").scope \
        == "fact.butterfly.attn_qkv"
    assert Linear(fact, 16, 32, site="mlp").scope == "fact.pixelfly.mlp"
    assert Linear(fact, 16, 16, site="head").scope == "dense.head"

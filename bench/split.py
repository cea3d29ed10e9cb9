"""Where one window of a cell spends its time, for the program's own layers.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets a cell up as ``bench/run.py`` does and serves one window, without the
reference check.  Prints one JSON object: the engine's counters over the
window (host time and dispatch time per decode step) and, traced, the
decode program's device time by named scope (ms per execution, and the
share of it any scope covers), the mean length of each ``serve.*`` span
per decode step, the ten longest idle gaps of device 0 each given to the
span innermost over most of it (``bench.*`` and ``serve.*``), and the
cell's per-layer metrics.  On a tree whose program has no spans or
scopes those parts come out empty.
"""
import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".bench_cache",
                                                       "jax")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

import argparse  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

from bench import harness, scopes, trace_reduce  # noqa: E402

MODULE = "jit_step_fn"


def _ms(totals: dict, n: int, top: int | None = None) -> dict:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return {str(k): v / n / 1e6 for k, v in ranked}


def device_split(run) -> dict:
    """ms per decode execution by scope and by scope and op group, and
    the share of the self time any scope covers."""
    table = scopes.program_scopes(MODULE)
    if not table:
        return {}
    events, plane = run.trace["events"], run.trace["plane"]
    times, n = scopes.scope_times(events, plane, MODULE, table)
    if not n:
        return {}
    ops: dict = collections.defaultdict(float)
    execs = trace_reduce.module_events(events, plane, MODULE)
    for e, t in trace_reduce.self_times(
            trace_reduce.ops_within(events, plane, execs)):
        scope = table.get(trace_reduce.op_name(e.name))
        ops[f"{scope}/{trace_reduce.op_group(e.name)}"] += t
    total = sum(times.values())
    return {"executions": n, "ms": _ms(times, n), "ops_ms": _ms(ops, n, 15),
            "scoped_pct": 100.0 * (total - times.get(None, 0.0)) / total}


def host_split(run) -> dict:
    """Mean ms per decode step of each serve.* span name inside it."""
    steps = scopes.decode_steps(run.trace["events"])
    if not steps:
        return {}
    per: dict = collections.defaultdict(float)
    for st, kids in steps:
        per["serve.step"] += st.dur_ns
        for k in kids:
            per[k.name] += k.dur_ns
    host = sorted(scopes.step_host_ns(st, kids) for st, kids in steps)
    return {"decode_steps": len(steps), "ms": _ms(per, len(steps)),
            "step_host_ms_median": host[len(host) // 2] / 1e6,
            "step_host_ms_max": host[-1] / 1e6}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    b = harness.Bench(cell, args.seed, t_start=T_START)
    trace_dir = tempfile.mkdtemp(prefix="bench-split-") if args.trace \
        else None
    run = b.window(args.seconds, args.seed, trace_dir=trace_dir)
    st = run.stats
    steps = st["decode_steps"] or 1
    walls = [s.t1 - s.t0 for s in run.window_steps()
             if s.decode_contexts and not s.prefill_lens]
    out = {"workload": cell.name, "seed": args.seed, "setup_s": b.setup_s,
           "decode_steps": st["decode_steps"],
           "host_ms_per_decode_step": 1e3 * st["host_time"] / steps,
           "decode_ms_per_step": 1e3 * st["decode_time"] / steps,
           # a decode step's wall time, as the client sees its step() call
           "decode_step_wall_ms": 1e3 * sum(walls) / max(len(walls), 1),
           "stats": st}
    if trace_dir is not None:
        t_reduce = time.monotonic()
        run.trace = harness.reduce_trace(trace_dir, run.trace_span)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ev, dev = run.trace["events"], run.trace["plane"]
        spans = trace_reduce.host_spans(ev) + scopes.serve_spans(ev)
        t0 = min(e.start_ns for e in trace_reduce.host_spans(ev))
        t1 = max(e.end_ns for e in trace_reduce.host_spans(ev))
        out.update(
            device=device_split(run), host=host_split(run),
            idle_gaps=scopes.attribute_gaps(
                trace_reduce.idle_gaps(ev, dev, t0, t1), spans),
            device_idle_pct=100.0 * (1 - run.trace["busy0_s"]
                                     / run.trace["window_s"]),
            metrics={m["name"]: harness.reader(m["name"])(run)
                     for m in cell.per_layer},
            reduce_s=time.monotonic() - t_reduce)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

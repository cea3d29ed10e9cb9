"""One benchmark run: set-up, warm-up, the measured window, the metrics and
the comparison with the plain reference.

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py`` and the limits of its comparison in
``bench/limits/<cell>.json``.  The system under test is driven through
its serving entry: the serve CLI's ``build_engine``, then
``Engine.submit`` and ``Engine.step``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import trace_reduce
from bench.model import arch, load_config, model_config, reference
from bench.peaks import peaks_for
from bench.traffic_gen import Plan, load_traffic, make_plan

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

# JAX monitoring events that mark a program lowered in this process: one
# per compile, whether the executable then comes from the persistent cache
# or from the compiler
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Cell ``name`` of ``root``/BENCHMARK.json, with the files it names
    under ``root``/bench."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    data = root / "bench"
    limits = json.loads((data / "limits" / f"{name}.json").read_text())
    return Cell(name, load_config(w["config"], data / "configs"),
                load_traffic(w["traffic"], data / "traffic"),
                int(w["chips"]),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)],
                limits)


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- records ---
@dataclasses.dataclass
class Req:
    index: int
    due: float            # monotonic seconds
    submitted: float
    seq: object           # the program's Sequence (host timestamps, tokens)
    slot: int | None = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_contexts: list   # per decode row: positions it attends to
    prefill_lens: list      # prompt lengths prefilled in this step
    pages_allocated: int | None
    pages_charged: int | None


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""

    config: dict
    traffic: dict
    chips: int
    tp: int
    peaks: object
    setup_s: float
    window: tuple[float, float]
    requests: list
    steps: list
    stats: dict             # EngineStats deltas over the window
    compiles_in_window: int
    trace_span: list | None = None  # monotonic start and end of the trace
    trace: dict | None = None   # see reduce_trace

    def in_window(self, t: float | None) -> bool:
        return t is not None and self.window[0] <= t < self.window[1]

    def window_steps(self) -> list:
        return [s for s in self.steps if self.in_window(s.t0)
                and s.t1 <= self.window[1]]


# ------------------------------------------------------------- set-up ----
# share of the device's memory left free beyond what the compiled
# programs report: the allocator's fragmentation and the eager ops of the
# cache insert and eviction
MEMORY_MARGIN = 0.04


def check_device(chips: int, require_tpu: bool = True):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX backend is "
                         f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    return devices


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


def _bucket(n: int, cap: int) -> int:
    """The runner's power-of-two bucket of ``n`` under ``cap``."""
    return min(_pow2(n), _pow2(cap))


def program_memory(cell: Cell, cfg, params, mesh) -> dict:
    """Per-device bytes of the two programs that bound the KV pool, from
    their compiled memory analysis: the largest prefill the traffic can
    reach (``max_wave_rows`` rows at the widest prompt) and the decode
    step over every slot.  Read from a runner built like the engine's,
    holding a one-page pool."""
    from jax.sharding import PartitionSpec as P
    from repro.serving.runner import ModelRunner

    t = cell.traffic
    slots, max_len = t["max_slots"], t["max_len"]
    probe = ModelRunner(params, cfg, max_len=max_len, num_slots=slots,
                        page_size=int(cell.config["page_size"]), num_pages=1,
                        mesh=mesh)
    rows = _bucket(t["max_wave_rows"], slots)
    width = _bucket(t["prompt"]["max"], max_len)
    dpa = probe._dpa()
    z = lambda *shape, dt=np.int32: np.zeros(shape, dt)  # noqa: E731
    with probe._trace_ctx():
        pf = probe._prefill.lower(
            probe.params, probe._put(z(rows, width), P(dpa, None)),
            probe._put(z(rows), P(dpa)), probe._put(z(rows, dt=np.float32),
                                                    P(dpa)),
            probe._put(z(rows), P(dpa)), probe._put(z(rows, dt=np.uint32),
                                                    P(dpa)),
            ragged=True).compile().memory_analysis()
        st = probe._step.lower(
            probe.params, probe.cache.data, probe.cache.table_device(),
            probe._tok, probe._pos, probe._temps, probe._topk,
            probe._seeds).compile().memory_analysis()
    return {"prefill_temp": int(pf.temp_size_in_bytes),
            "prefill_out": int(pf.output_size_in_bytes),
            "decode_temp": int(st.temp_size_in_bytes)}


def pool_bytes(limit: int, weights: int, prog: dict) -> int:
    """Per-device bytes of the KV pool: the most that leaves room, beside
    the weights, for a prefill (its temporaries and its output caches),
    for the cache insert after it and for a decode step.  The decode step
    and the insert each return a new pool without donating the old one,
    so both hold two pools at once.  The insert also holds the prefill's
    caches, a copy of one of their two leaves (``take``) and, for its eager
    scatter, a temporary of that leaf again, which no compiled program of
    the runner reports; on a v5e the insert of an 8 x 1024 wave failed at
    a pool planned for exactly that (``jit_scatter`` wanted 864 MiB with
    645 MiB free), so the insert is charged a further copy of the caches."""
    free = int(limit * (1 - MEMORY_MARGIN)) - weights
    out = prog["prefill_out"]
    return min(free - prog["prefill_temp"] - out,
               (free - 3 * out) // 2,
               (free - prog["decode_temp"]) // 2)


def build(cell: Cell, seed: int, memory_bytes: int | None = None):
    """(engine, cfg, mesh, plan report, memory) for ``cell``, weights from
    ``seed``."""
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.serve import build_engine, build_parser
    from repro.models import init_params
    from repro.serving.budget import param_bytes, plan_engine_report
    from bench.weights import make_like

    c, t = cell.config, cell.traffic
    cfg = model_config(c)
    dp, tp = c["mesh"]["dp"], c["mesh"]["tp"]
    mesh = make_serving_mesh(dp, tp) if dp * tp > 1 else None
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    shardings = None
    if mesh is not None:
        from repro.parallel.sharding import partition_params, to_named
        shardings = to_named(mesh, partition_params(cfg, mesh))
    params = make_like(seed, shapes, shardings, arch(c))
    if memory_bytes is None:
        memory_bytes = min(d.memory_stats()["bytes_limit"]
                           for d in jax.devices()[:dp * tp])
    weights = param_bytes(cfg, mesh=mesh)
    memory = program_memory(cell, cfg, params, mesh)
    memory.update(limit=int(memory_bytes), weights=int(weights),
                  pool=pool_bytes(memory_bytes, weights, memory))
    if memory["pool"] <= 0:
        raise SystemExit(f"bench: {c['name']}: no room for a KV pool: "
                         f"{memory}")
    ps = int(c["page_size"])
    plan = plan_engine_report(cfg, weights + memory["pool"], t["max_len"],
                              max_slots=t["max_slots"], mesh=mesh,
                              page_size=ps)
    args = build_parser().parse_args([
        "--slots", str(plan.num_slots),
        "--token-budget", str(plan.num_pages * ps),
        "--page-size", str(ps), "--max-len", str(t["max_len"]),
        "--dp", str(dp), "--tp", str(tp)])
    engine = build_engine(args, cfg, params, t["max_len"], mesh)
    return engine, cfg, mesh, plan, memory


def warm_waves(lens, max_len: int, slots: int, max_rows: int):
    """Admission waves that reach every prefill program the traffic can:
    each (rows bucket, width bucket, ragged) of the runner's power-of-two
    bucketing, for waves of up to ``max_rows`` requests, and every real
    wave size (the cache insert is traced per size).  Returns one list of
    prompt lengths per wave."""
    lens = sorted(set(int(x) for x in lens))
    lo = lens[0]
    widths = sorted({_bucket(n, max_len) for n in lens})
    waves = []
    for rb in sorted({_bucket(r, slots) for r in range(1, max_rows + 1)}):
        for w in widths:
            short = [n for n in lens if _bucket(n, max_len) == w and n < w]
            # ragged: a real row shorter than the width, or a dummy row
            if short:
                waves.append([max(short)] * rb)
            elif lo < w and rb > 1:
                waves.append([w] + [lo] * (rb - 1))
            elif rb > 2:
                waves.append([w] * (rb - 1))
            # every row exactly the width
            if w in lens:
                waves.append([w] * rb)
    sizes = {len(x) for x in waves}
    waves += [[lens[len(lens) // 2]] * r for r in range(1, max_rows + 1)
              if r not in sizes]
    return waves


def warm_up(engine, waves, vocab: int) -> None:
    """Drive each wave through admission, prefill, two decode steps and
    retirement, so every program the window can reach is compiled."""
    from repro.serving import Request
    rng = np.random.default_rng(0)
    for k, wave in enumerate(waves):
        t0 = time.monotonic()
        for j, n in enumerate(wave):
            engine.submit(Request(
                request_id=f"warm{k}.{j}",
                prompt=tuple(int(x) for x in rng.integers(0, vocab, n)),
                max_new=3))
        guard = 0
        while engine.scheduler.has_work:
            engine.step()
            guard += 1
            if guard > 100:
                raise RuntimeError("warm-up wave did not drain")
        print(f"bench: warm-up wave {k + 1}/{len(waves)} "
              f"{len(wave)} x {max(wave)}: {time.monotonic() - t0:.1f} s",
              file=sys.stderr, flush=True)


class Bench:
    """A cell's engine in one process: built from the seed's weights and
    warmed for every shape the cell's traffic reaches (the set-up), then
    serving measured windows.  ``run`` makes one window; the knee sweep
    makes one per offered rate."""

    def __init__(self, cell: Cell, seed: int, *, t_start=None,
                 require_tpu: bool = True, memory_bytes: int | None = None,
                 fault=None):
        t_start = time.monotonic() if t_start is None else t_start
        self.cell = cell
        self.devices = check_device(cell.chips, require_tpu)
        kind = self.devices[0].device_kind
        self.peaks = peaks_for(kind) if require_tpu else None
        self.engine, self.cfg, _, self.plan, self.memory = build(
            cell, seed, memory_bytes)
        print(f"bench: slots {self.engine.num_slots}, pages "
              f"{self.engine.num_pages}, bytes {self.memory}",
              file=sys.stderr, flush=True)
        if fault is not None:
            fault(self.engine)
        t = cell.traffic
        lo, hi = t["prompt"]["min"], t["prompt"]["max"]
        warm_up(self.engine, warm_waves(range(lo, hi + 1), t["max_len"],
                                        self.engine.num_slots,
                                        t["max_wave_rows"]),
                self.cfg.vocab_size)
        self.setup_s = time.monotonic() - t_start
        print(f"bench: set-up {self.setup_s:.1f} s", file=sys.stderr,
              flush=True)
        self.compiles = _compile_counter()
        self.windows = 0

    def window(self, seconds: float, seed: int, *, rate=None,
               trace_dir=None) -> Run:
        """Serve the cell's traffic from ``seed`` (at ``rate`` requests a
        second where given) through a ramp and a window of ``seconds``,
        until every request due in the window has its first token."""
        cell = self.cell
        t = dict(cell.traffic)
        if rate is not None:
            t["rate_per_s"] = rate
        plan = make_plan(t, seed, seconds, self.engine.num_slots,
                         self.cfg.vocab_size)
        e = self.engine
        st0 = dataclasses.asdict(e.stats)
        c0 = self.compiles[0]
        self.windows += 1
        reqs, steps, window, span = drive(e, t, plan, seconds, trace_dir,
                                          id_prefix=f"w{self.windows}.")
        st1 = dataclasses.asdict(e.stats)
        return Run(cell.config, t, cell.chips, cell.config["mesh"]["tp"],
                   self.peaks, self.setup_s, window, reqs, steps,
                   {k: st1[k] - st0[k] for k in st0},
                   self.compiles[0] - c0, trace_span=span)

    def drain(self) -> None:
        """Serve whatever the engine still holds to its end."""
        while self.engine.scheduler.has_work:
            self.engine.step()

    def memory_peak(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices[:self.cell.chips])

    def close(self) -> None:
        """Free the program's weights and KV pool."""
        self.engine = None
        gc.collect()


# ------------------------------------------------------------- window ----
def _compile_counter():
    import jax
    count = [0]

    def on_duration(event, duration, **_):
        if event == LOWERING_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return count


def drive(engine, t: dict, plan: Plan, seconds: float, trace_dir=None,
          id_prefix: str = "r", grace: float = 60.0):
    """The ramp, the measured window, and the wait after it for the first
    token of every request due in the window (at most ``grace`` seconds).

    The client keeps at most ``max_wave_rows`` requests waiting in the
    engine and holds the rest itself, each still timed from when it was
    due: the engine prefills every waiting request that fits in one wave,
    and larger waves than that do not fit in the chip's memory beside the
    weights.  Returns (requests, steps, window, trace span (monotonic) or
    None)."""
    import jax
    from repro.serving import Request, SamplingParams

    closed = t["loop"] == "closed"
    hold = int(t["max_wave_rows"])
    sched = engine.scheduler
    alloc = getattr(engine.cache, "allocator", None)
    reqs: dict[str, Req] = {}
    steps: list[Step] = []
    start = time.monotonic()
    w0 = start + t["ramp_seconds"]
    w1 = w0 + seconds
    trace_span = None
    if trace_dir is not None:
        ts = min(t["trace_seconds"], seconds)
        trace_span = [w0 + (seconds - ts) / 2, w0 + (seconds + ts) / 2]
    tracing = False
    nxt = 0
    client_of: dict[str, int] = {}
    pending_clients: list[tuple[float, int]] = []  # (due, client)
    if closed:
        pending_clients = [(start + float(s), c)
                           for c, s in enumerate(plan.client_starts)]

    def next_due():
        """When the client's next request is due, or None."""
        if closed:
            pending_clients.sort()
            return pending_clients[0][0] if pending_clients else None
        return start + float(plan.due[nxt]) if nxt < len(plan) else None

    def submit(due: float, client: int | None = None):
        # a closed loop goes through its plan as often as it needs
        nonlocal nxt
        i = nxt % len(plan)
        rid = f"{id_prefix}{nxt}"
        nxt += 1
        req = Request(request_id=rid, prompt=plan.prompt(i),
                      max_new=int(plan.output_lens[i]),
                      sampling=SamplingParams())
        seq = engine.submit(req)
        reqs[rid] = Req(i, due, time.monotonic(), seq)
        if client is not None:
            client_of[rid] = client

    def served() -> bool:
        """Every request due in the window has its first token."""
        due = next_due()
        if due is not None and due < w1:
            return False
        return all(r.seq.t_first_token is not None for r in reqs.values()
                   if w0 <= r.due < w1)

    while True:
        now = time.monotonic()
        if trace_span is not None:
            if not tracing and trace_span[0] <= now < trace_span[1]:
                jax.profiler.start_trace(trace_dir)
                tracing = True
                trace_span[0] = time.monotonic()
            elif tracing and now >= trace_span[1]:
                jax.profiler.stop_trace()
                tracing = False
                trace_span[1] = time.monotonic()
        if now >= w1 and (now >= w1 + grace or served()):
            break
        with jax.profiler.TraceAnnotation("bench.submit"):
            while len(sched.waiting) < hold:
                due = next_due()
                if due is None or due > now or due >= w1:
                    break
                if closed:
                    submit(*pending_clients.pop(0))
                else:
                    submit(due)
        if not sched.has_work:
            nd = next_due()
            nd = w1 if nd is None or nd >= w1 else nd
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nd, w1) - time.monotonic()))
            continue
        active = list(sched.active.values())
        before = {s.request_id for s in active}
        decode_ctx = [s.prompt_len + len(s.tokens) for s in active
                      if s.tokens]
        st = engine.stats
        d0, p0 = st.decode_steps, st.prefill_dispatches
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.step"):
            events = engine.step()
        t1 = time.monotonic()
        is_decode = st.decode_steps > d0
        admitted = [] if st.prefill_dispatches == p0 else [
            s for s in sched.active.values() if s.request_id not in before]
        steps.append(Step(
            t0, t1, decode_ctx if is_decode else [],
            [s.prompt_len for s in admitted],
            None if alloc is None else alloc.num_pages - alloc.num_free,
            getattr(sched, "reserved_units", None)))
        for e in events:
            r = reqs.get(e.request_id)
            if r is None:
                continue
            if e.index == 0 and r.slot is None:
                r.slot = r.seq.slot
            if e.finish_reason is not None and closed:
                pending_clients.append((t1, client_of[e.request_id]))
    if tracing:
        jax.profiler.stop_trace()
        trace_span[1] = time.monotonic()
    return list(reqs.values()), steps, (w0, w1), trace_span


# ------------------------------------------------------------- trace -----
def reduce_trace(trace_dir: str, span) -> dict:
    """The device numbers of a traced stretch, on device 0."""
    events = trace_reduce.load_events(trace_dir)
    planes = trace_reduce.device_planes(events)
    if not planes:
        raise RuntimeError("the trace holds no device plane")
    dev = planes[0]
    spans = trace_reduce.host_spans(events)
    if not spans:
        raise RuntimeError("the trace holds none of the benchmark's spans")
    t0 = min(e.start_ns for e in spans)
    t1 = max(e.end_ns for e in spans)
    gaps = trace_reduce.idle_gaps(events, dev, t0, t1)
    busy = [trace_reduce.busy_ns(events, p, t0, t1) / 1e9 for p in planes]
    return {
        "events": events, "plane": dev, "planes": planes,
        "span": tuple(span), "window_s": (t1 - t0) / 1e9,
        "busy_s": float(np.mean(busy)), "busy0_s": busy[0],
        "breakdown": {
            "device_ops": trace_reduce.top_ops(events, dev),
            "idle_gaps": trace_reduce.attribute_gaps(gaps, spans)},
    }


# --------------------------------------------------------- correctness ---
def pick_sample(reqs: list, n: int, seed: int) -> list:
    """Finished requests to compare: the longest, then one per slot in an
    order drawn from the seed, then more from the seed, up to ``n``."""
    done = [r for r in reqs if r.seq.done and r.seq.tokens]
    if not done:
        return []
    rng = np.random.default_rng([seed % 2 ** 64, 7])
    longest = max(done, key=lambda r: r.seq.prompt_len + len(r.seq.tokens))
    out = [longest]
    by_slot: dict = {}
    for r in rng.permutation(len(done)):
        by_slot.setdefault(done[r].slot, done[r])
    for slot in sorted(by_slot, key=lambda s: (s is None, s)):
        if by_slot[slot] is not longest:
            out.append(by_slot[slot])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] not in out]
    return (out + rest)[:n]


def compare(cell: Cell, seed: int, sample: list, max_len: int,
            control: bool = False) -> dict:
    """Run the reference over each sampled prompt with its served tokens.

    Returns ``widest_gap``: over every served token, how far its
    reference logit lies below the reference's best logit, in units of
    the standard deviation of that position's reference logits.  With
    ``control`` also ``control_gap``: the same for the token the float8
    control puts first at each of those positions."""
    ref = reference(cell.config).Reference(cell.config, seed)
    n = len(sample)
    block = 4
    rows = -(-max(n, 1) // block) * block
    tokens = np.zeros((rows, max_len), np.int32)
    flat, served = [], []
    for j, r in enumerate(sample):
        p = list(r.seq.request.prompt)
        out = list(r.seq.tokens)
        seq = (p + out[:-1])[:max_len]
        tokens[j, :len(seq)] = seq
        flat += [j * max_len + len(p) - 1 + i for i in range(len(out))]
        served += out
    flat = np.asarray(flat, np.int64)
    served = np.asarray(served)
    result = {"requests": n, "tokens": int(len(served))}
    if not n:
        result["widest_gap"] = float("inf")
        return result
    ids = served[:, None]
    if control:
        hc = ref.hidden(tokens, "fp8", block=block)
        top = ref.summary(hc, flat, ids, "fp8")["argmax"]
        del hc
        ids = np.stack([served, top], axis=1)
    h = ref.hidden(tokens, "f32", block=block)
    st = ref.summary(h, flat, ids, "f32")
    gaps = (st["best"][:, None] - st["at"]) / st["std"][:, None]
    result["widest_gap"] = float(gaps[:, 0].max())
    if control:
        result["control_gap"] = float(gaps[:, 1].max())
    return result


# ------------------------------------------------------------------ run --
def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, require_tpu: bool = True,
        memory_bytes: int | None = None, fault=None,
        control: bool = False) -> dict:
    """One run; returns the result object the command prints.  With
    ``control`` the checks judge the float8 control's tokens in place of
    the served ones, so a sound program comes out as not correct."""
    b = Bench(cell, seed, t_start=t_start, require_tpu=require_tpu,
              memory_bytes=memory_bytes, fault=fault)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    rec = b.window(seconds, seed, trace_dir=trace_dir)
    devices = b.devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": b.memory_peak()}
    due = [r for r in rec.requests if rec.in_window(r.due)]
    result = {"correct": False, "attempted": len(due),
              "failed": sum(1 for r in due if r.seq.t_first_token is None),
              "metrics": {}, "device": device}
    metrics = cell.per_layer if trace else cell.end_to_end
    if trace:
        rec.trace = reduce_trace(trace_dir, rec.trace_span)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        result["breakdown"] = rec.trace["breakdown"]
    for m in metrics:
        v = reader(m["name"])(rec)
        if v is not None:
            result["metrics"][m["name"]] = {"value": float(v),
                                            "unit": m["unit"]}

    # the program's state goes before the reference runs
    sample = pick_sample(rec.requests, cell.traffic["check_requests"], seed)
    b.close()
    t0 = time.monotonic()
    check = compare(cell, seed, sample, cell.traffic["max_len"],
                    control=control)
    print(f"bench: reference check {time.monotonic() - t0:.1f} s",
          file=sys.stderr, flush=True)
    result["sample"] = {"requests": check["requests"],
                        "tokens": check["tokens"]}
    if control:
        result["served_widest_gap"] = check["widest_gap"]
        check["widest_gap"] = check["control_gap"]
    checks = {k: {"value": check[k], "limit": cell.limits[k]}
              for k in cell.limits}
    checks["failed"] = {"value": result["failed"], "limit": 0}
    result["correct"] = all(v["value"] <= v["limit"]
                            for v in checks.values()) and bool(sample)
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the float8 control's tokens in place of the "
                         "served ones (a sound program then fails)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    result = run(cell, args.seed, args.seconds, bool(args.trace),
                 t_start=t_start, control=bool(args.control))
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0

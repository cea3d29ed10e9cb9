"""From a profiler trace to the device numbers the metrics read.

A trace is reduced to plain events, ``Event(plane, line, name, start_ns,
dur_ns)``, so everything below works the same on a recorded trace and on a
synthetic one built in a test.  Device planes are named ``/device:TPU:<n>``;
on each, the ``XLA Modules`` line holds one event per execution of a
compiled program (named after the jitted function, ``jit_step_fn`` for the
decode step) and the ``XLA Ops`` line one event per operation.  Host planes
hold the benchmark's own spans (``bench.step``, ``bench.idle``, ...).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_events(trace_dir: str) -> list[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_planes(events: list[Event]) -> list[str]:
    names = {e.plane for e in events if e.plane.startswith("/device:")}
    return sorted(names, key=lambda n: int(re.sub(r"\D", "", n) or 0))


def on(events, plane: str, line: str) -> list[Event]:
    return [e for e in events if e.plane == plane and e.line == line]


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(events, plane: str, t0: float = -math.inf,
            t1: float = math.inf) -> float:
    """Union of the intervals in [t0, t1] in which an operation ran on
    ``plane``."""
    return union_ns((max(e.start_ns, t0), min(e.end_ns, t1))
                    for e in on(events, plane, OPS_LINE)
                    if e.end_ns > t0 and e.start_ns < t1)


def idle_gaps(events, plane: str, t0: float, t1: float):
    """(start, end) of every interval in [t0, t1] with no operation."""
    ivs = sorted((max(e.start_ns, t0), min(e.end_ns, t1))
                 for e in on(events, plane, OPS_LINE)
                 if e.end_ns > t0 and e.start_ns < t1)
    gaps, cur = [], t0
    for s, e in ivs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def op_name(name: str) -> str:
    """An op event's HLO name: a TPU trace names the event by the whole
    instruction (``%fusion.12 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_group(name: str) -> str:
    """An op's name without its numeric suffix (``fusion.12`` -> ``fusion``)."""
    return re.sub(r"[.:]\d+$", "", op_name(name))


def program_name(name: str) -> str:
    """A compiled program's name without its id (``jit_step_fn(7)``)."""
    return re.sub(r"\(\d+\)$", "", name)


def self_times(ops: list[Event]) -> list[tuple[Event, float]]:
    """(op, ns) of each op less the ops nested inside it (a loop's body is
    traced inside the loop's own event), so no time counts twice."""
    out, stack = [], []
    for e in sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= e.start_ns:
            out.append(stack.pop())
        if stack and e.end_ns <= stack[-1][0].end_ns:
            stack[-1][1] -= e.dur_ns
        stack.append([e, e.dur_ns])
    out += stack
    return [(e, max(0.0, t)) for e, t in out]


def module_events(events, plane: str, module: str) -> list[Event]:
    """Executions of the compiled program named ``module`` (a prefix of
    the event name, which may carry an id)."""
    return [e for e in on(events, plane, MODULES_LINE)
            if e.name == module or e.name.startswith(module + "(")
            or e.name.startswith(module + ".")]


def ops_within(events, plane: str, spans) -> list[Event]:
    """Ops on ``plane`` that start inside one of ``spans`` (Events)."""
    bounds = sorted((s.start_ns, s.end_ns) for s in spans)
    out = []
    for e in on(events, plane, OPS_LINE):
        for s, t in bounds:
            if s <= e.start_ns < t:
                out.append(e)
                break
    return out


def is_collective(name: str) -> bool:
    return any(c in op_name(name) for c in COLLECTIVES)


def top_ops(events, plane: str, n: int = 10) -> list[list]:
    """[["<program>/<op group>", seconds], ...]: where the device time went,
    by the self time of each op, longest first."""
    mods = sorted(on(events, plane, MODULES_LINE), key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    tot: dict[str, float] = {}
    for e, t in self_times(on(events, plane, OPS_LINE)):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        g = op_group(e.name)
        if i >= 0 and e.start_ns < mods[i].end_ns:
            g = f"{program_name(mods[i].name)}/{g}"
        tot[g] = tot.get(g, 0.0) + t
    return [[k, v / 1e9] for k, v in sorted(tot.items(),
                                            key=lambda kv: -kv[1])[:n]]


def host_spans(events, prefix: str = "bench.") -> list[Event]:
    return [e for e in events if not e.plane.startswith("/device:")
            and e.name.startswith(prefix)]


def attribute_gaps(gaps, spans: list[Event], n: int = 10) -> list[list]:
    """[[host span, seconds], ...] for the ``n`` longest idle gaps: the
    benchmark span that overlaps each gap most (``unattributed`` when
    none does)."""
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, best_ov = "unattributed", 0.0
        for sp in spans:
            ov = min(t, sp.end_ns) - max(s, sp.start_ns)
            if ov > best_ov:
                best, best_ov = sp.name, ov
        out.append([best, (t - s) / 1e9])
    return out

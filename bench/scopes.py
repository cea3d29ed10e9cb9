"""Device time by named scope, and the serving program's host spans.

The program names the parts of its compiled programs with named scopes
(``fact.<kind>.<site>``, ``dense.<site>``, ``attn.*``, ``sample``,
``norm``, ``embed``, ``layers``) and the stretches of its host time with
``serve.*`` spans (``repro.serving.tracing``).  A TPU trace's op events
carry neither: an op event is named by its compiled instruction alone, so
an op's scope is read from the compiled program's text, where each
instruction's ``op_name`` metadata holds the scopes it was traced under.

An op is credited to the innermost scope of its ``op_name``.  A fusion (or
an async op) whose computation holds a matmul is credited to the matmul's
scope, since the matmul is its work; any other to its own
metadata, which is its root's, and where that holds no scope, to the scope
most of its fused instructions carry.

Everything here reads plain events and text, so it works the same on a
recorded trace and on a synthetic one; where the program publishes no
text or spans (a tree before they existed), the readers get None.
"""
from __future__ import annotations

import bisect
import collections
import re

from bench import trace_reduce

SCOPE_PREFIXES = ("fact.", "dense.", "attn.")
SCOPE_NAMES = ("sample", "norm", "embed", "layers")
MATMULS = ("convolution", "dot")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"(?<![\w%.\-])([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# a fusion's or async op's computation; a loop's body is not its work
# (the body's ops are traced as events of their own)
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The innermost scope of the vocabulary in an ``op_name`` path
    (``jit(step_fn)/layers/while/body/fact.butterfly.attn_qkv/dot_general``
    -> ``fact.butterfly.attn_qkv``), or None."""
    for part in reversed(op_name.split("/")):
        if part.startswith(SCOPE_PREFIXES) or part in SCOPE_NAMES:
            return part
    return None


def op_scopes(hlo_text: str) -> dict[str, str]:
    """{instruction name: scope} for every instruction of a compiled
    program's text that can be credited to a scope (module docstring)."""
    comps: dict[str, list] = {}
    own: dict[str, str | None] = {}
    calls: dict[str, list] = {}
    opcode: dict[str, str] = {}
    cur = None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h and not line.startswith(" "):
            cur = comps.setdefault(h.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        opcode[name] = op.group(1) if op else ""
        meta = _OP_NAME.search(rest)
        own[name] = scope_of(meta.group(1)) if meta else None
        calls[name] = _CALLS.findall(rest)
        cur.append(name)

    def inner(comp: str, seen: set) -> list[str]:
        """Instructions of ``comp`` and of what it calls."""
        if comp in seen:
            return []
        seen.add(comp)
        out = []
        for n in comps.get(comp, []):
            out.append(n)
            for c in calls.get(n, []):
                out += inner(c, seen)
        return out

    scopes = {}
    for name, scope in own.items():
        body = [n for c in calls[name] for n in inner(c, set())]
        mm = [own[n] for n in body if opcode[n] in MATMULS and own[n]]
        if mm:
            scope = mm[0]
        elif scope is None:
            counted = collections.Counter(own[n] for n in body if own[n])
            scope = counted.most_common(1)[0][0] if counted else None
        if scope is not None:
            scopes[name] = scope
    return scopes


def program_scopes(module: str) -> dict[str, str] | None:
    """{instruction name: scope} of the compiled programs named ``module``
    that the program under test has run in this process, or None where it
    publishes none."""
    try:
        from repro.serving.tracing import program_texts
    except ImportError:
        return None
    texts = program_texts(module)
    if not texts:
        return None
    out = {}
    for t in texts:
        out.update(op_scopes(t))
    return out or None


def scope_times(events, plane: str, module: str,
                scopes: dict[str, str]) -> tuple[dict[str, float], int]:
    """({scope: self ns}, executions) over the ops inside the executions
    of ``module`` on ``plane``; ops with no scope count under None."""
    execs = trace_reduce.module_events(events, plane, module)
    ops = trace_reduce.ops_within(events, plane, execs)
    out: dict = collections.defaultdict(float)
    for e, t in trace_reduce.self_times(ops):
        out[scopes.get(trace_reduce.op_name(e.name))] += t
    return dict(out), len(execs)


def scoped_ms(run, module: str, prefix: str) -> float | None:
    """Self time per execution of ``module``, in ms, of the ops under
    scopes starting with ``prefix``: None without a trace, without
    executions, or where no op of the program has a scope."""
    if run.trace is None:
        return None
    scopes = program_scopes(module)
    if not scopes:
        return None
    times, n = scope_times(run.trace["events"], run.trace["plane"], module,
                           scopes)
    if not n or not any(k is not None for k in times):
        return None
    return sum(t for k, t in times.items()
               if k is not None and k.startswith(prefix)) / n / 1e6


# ----------------------------------------------------------- host spans --
def serve_spans(events) -> list:
    return trace_reduce.host_spans(events, "serve.")


def decode_steps(events) -> list[tuple]:
    """(step span, its spans) for each ``serve.step`` whose only dispatch
    was a decode: it waited on ``serve.wait.decode`` and on no other
    target dispatch."""
    spans = serve_spans(events)
    by_thread = collections.defaultdict(list)
    for s in spans:
        by_thread[(s.plane, s.line)].append(s)
    out = []
    for thread in by_thread.values():
        thread.sort(key=lambda s: s.start_ns)
        starts = [s.start_ns for s in thread]
        for st in thread:
            if st.name != "serve.step":
                continue
            lo = bisect.bisect_left(starts, st.start_ns)
            hi = bisect.bisect_right(starts, st.end_ns)
            kids = [s for s in thread[lo:hi]
                    if s is not st and s.end_ns <= st.end_ns]
            waits = {s.name for s in kids
                     if s.name.startswith("serve.wait.")}
            if waits == {"serve.wait.decode"}:
                out.append((st, kids))
    return out


def step_host_ns(step, kids) -> float:
    """A step's length less the time it waited for its dispatches."""
    return step.dur_ns - sum(s.dur_ns for s in kids
                             if s.name.startswith("serve.wait."))


def attribute_gaps(gaps, spans, n: int = 10) -> list[list]:
    """[[span, seconds], ...] for the ``n`` longest idle gaps: each goes to
    the span that is innermost (the shortest of those open) over the most
    of it, ``unattributed`` where no span is open."""
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        open_ = [sp for sp in spans if sp.start_ns < t and sp.end_ns > s]
        cuts = sorted({s, t} | {x for sp in open_
                                for x in (sp.start_ns, sp.end_ns)
                                if s < x < t})
        held: dict = collections.defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            inner = [sp for sp in open_
                     if sp.start_ns <= a and b <= sp.end_ns]
            if inner:
                held[min(inner, key=lambda sp: sp.dur_ns).name] += b - a
        best = max(held, key=held.get) if held else "unattributed"
        out.append([best, (t - s) / 1e9])
    return out

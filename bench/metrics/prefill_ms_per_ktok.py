"""Device time of the prefill programs per 1000 prompt tokens, in ms, over
the traced stretch (runner)."""
from bench import trace_reduce

MODULE = "jit_prefill_fn"


def read(run):
    if run.trace is None:
        return None
    ev = trace_reduce.module_events(run.trace["events"], run.trace["plane"],
                                    MODULE)
    s0, s1 = run.trace["span"]
    tokens = sum(sum(s.prefill_lens) for s in run.steps
                 if s0 <= s.t0 and s.t1 <= s1)
    if not ev or not tokens:
        return None
    return sum(e.dur_ns for e in ev) / 1e6 / (tokens / 1000)

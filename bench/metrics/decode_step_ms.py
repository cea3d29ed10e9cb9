"""Device time of one decode step, in ms: the mean duration of the decode
program's executions on device 0 in the traced stretch (runner)."""
from bench import trace_reduce

MODULE = "jit_step_fn"


def read(run):
    if run.trace is None:
        return None
    ev = trace_reduce.module_events(run.trace["events"], run.trace["plane"],
                                    MODULE)
    return sum(e.dur_ns for e in ev) / len(ev) / 1e6 if ev else None

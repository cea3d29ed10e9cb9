"""Share of the decode program's device time on device 0 spent in
collectives (all-gather, all-reduce, reduce-scatter, collective-permute,
all-to-all), in % (device mesh)."""
from bench import trace_reduce

MODULE = "jit_step_fn"


def read(run):
    if run.trace is None:
        return None
    events, plane = run.trace["events"], run.trace["plane"]
    mods = trace_reduce.module_events(events, plane, MODULE)
    if not mods:
        return None
    ops = trace_reduce.ops_within(events, plane, mods)
    coll = sum(e.dur_ns for e in ops if trace_reduce.is_collective(e.name))
    return 100.0 * coll / sum(e.dur_ns for e in mods)

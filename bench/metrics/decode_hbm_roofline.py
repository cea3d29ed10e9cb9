"""Share of the HBM roofline the decode step reaches, in %: the fewest
bytes a step must read on one chip (weights under the cell's policy, of
expert leaves only the experts its steps read, plus the K/V of its live
tokens, bench/counts.py) at the chip's peak bandwidth, over the decode
program's device time, averaged over the traced steps (model step).
Nothing where the model has experts and the run no counter of those read."""
from bench import counts, trace_reduce

MODULE = "jit_step_fn"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    experts = counts.experts_read_per_step(run.config, run.stats)
    if experts is None:
        return None
    ev = trace_reduce.module_events(run.trace["events"], run.trace["plane"],
                                    MODULE)
    s0, s1 = run.trace["span"]
    steps = [s for s in run.steps if s.decode_contexts
             and s0 <= s.t0 and s.t1 <= s1]
    if not ev or not steps:
        return None
    need = sum(counts.decode_min_bytes_per_chip(
        run.config, run.tp, sum(s.decode_contexts), experts) for s in steps)
    t_min = need / len(steps) / run.peaks.hbm_bw
    t_dev = sum(e.dur_ns for e in ev) / len(ev) / 1e9
    return 100.0 * t_min / t_dev

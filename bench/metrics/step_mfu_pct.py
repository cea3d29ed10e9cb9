"""Model FLOP/s utilization of the whole step, in %: model FLOPs of every
prefill and decode token of the steps in the traced stretch
(bench/counts.py; an expert leaf at its token share), over those steps'
wall time (dispatch to results on the host) x chips x the chip's peak.
Nothing where the model has experts and the run no counter of those read."""
from bench import counts


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    if counts.experts_read_per_step(run.config, run.stats) is None:
        return None
    s0, s1 = run.trace["span"]
    steps = [s for s in run.steps if s0 <= s.t0 and s.t1 <= s1]
    wall = sum(s.t1 - s.t0 for s in steps)
    flops = sum(sum(counts.decode_token_flops(run.config, n)
                    for n in s.decode_contexts)
                + sum(counts.prefill_flops(run.config, n)
                      for n in s.prefill_lens) for s in steps)
    if not wall or not flops:
        return None
    return 100.0 * flops / (wall * run.chips * run.peaks.flops)

"""The tail of the gaps between tokens, in ms: the 95th percentile of every
gap between consecutive output tokens of a request that lies in the
window.  Per layer (scheduler), not end to end: it sits where gaps that
hold a 512-wide prefill meet gaps that hold a 1024-wide one, and jumps
between the two from run to run."""
import numpy as np


def read(run):
    v = [(b - a) * 1e3 for r in run.requests
         for a, b in zip(r.seq.t_tokens, r.seq.t_tokens[1:])
         if run.in_window(a) and run.in_window(b)]
    return float(np.percentile(v, 95)) if v else None

"""Median of every gap between consecutive output tokens of a request, in
ms, over the gaps that lie in the window."""
import numpy as np


def read(run):
    v = [(b - a) * 1e3 for r in run.requests
         for a, b in zip(r.seq.t_tokens, r.seq.t_tokens[1:])
         if run.in_window(a) and run.in_window(b)]
    return float(np.median(v)) if v else None

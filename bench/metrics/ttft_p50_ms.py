"""Median time to first token, in ms, over every request due in the
window, timed from when it was due to its first token (which may come
after the window closes)."""
import numpy as np


def read(run):
    v = [(r.seq.t_first_token - r.due) * 1e3 for r in run.requests
         if run.in_window(r.due) and r.seq.t_first_token is not None]
    return float(np.median(v)) if v else None

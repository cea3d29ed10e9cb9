"""The tail of time to first token, in ms: its 95th percentile over every
request due in the window, timed from when it was due to its first token
(which may come after the window closes).  Per layer (scheduler), not end
to end: at 0.8 x knee it swings by more than any bound can hold."""
import numpy as np


def read(run):
    v = [(r.seq.t_first_token - r.due) * 1e3 for r in run.requests
         if run.in_window(r.due) and r.seq.t_first_token is not None]
    return float(np.percentile(v, 95)) if v else None

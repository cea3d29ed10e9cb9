"""Median wait from a request's due time to its admission into a slot, in
ms, over the requests due in the window (scheduler)."""
import numpy as np


def read(run):
    v = [(r.seq.t_admitted - r.due) * 1e3 for r in run.requests
         if run.in_window(r.due) and r.seq.t_admitted is not None]
    return float(np.median(v)) if v else None

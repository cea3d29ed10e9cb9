"""Pages that hold live tokens (allocated from the pool) over pages that
admission charged (the scheduler's reserved units), in %, averaged over
the window's steps (KV cache)."""
import numpy as np


def read(run):
    v = [100.0 * s.pages_allocated / s.pages_charged
         for s in run.window_steps()
         if s.pages_allocated is not None and s.pages_charged]
    return float(np.mean(v)) if v else None

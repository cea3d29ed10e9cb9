"""Mean of every gap between consecutive output tokens of a request, in
ms, over the gaps that lie in the window: the time a streaming user waits
per token, prefill stalls included."""
import numpy as np


def read(run):
    v = [(b - a) * 1e3 for r in run.requests
         for a, b in zip(r.seq.t_tokens, r.seq.t_tokens[1:])
         if run.in_window(a) and run.in_window(b)]
    return float(np.mean(v)) if v else None

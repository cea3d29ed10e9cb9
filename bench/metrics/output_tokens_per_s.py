"""Output tokens produced in the window, per second of the window."""


def read(run):
    n = sum(1 for r in run.requests for t in r.seq.t_tokens
            if run.in_window(t))
    return n / (run.window[1] - run.window[0]) if n else None

"""Host time of one decode step, in ms: the mean over the traced stretch's
decode ``serve.step`` spans of each span's length less its ``serve.wait.*``
spans — scheduling, staging, launch, cache bookkeeping and emission
(engine core, host)."""
from bench import scopes


def read(run):
    if run.trace is None:
        return None
    steps = scopes.decode_steps(run.trace["events"])
    if not steps:
        return None
    return sum(scopes.step_host_ns(s, kids)
               for s, kids in steps) / len(steps) / 1e6

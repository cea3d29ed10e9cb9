"""Rows decoded per decode step over the window, from the engine's own
counters (scheduler)."""


def read(run):
    steps = run.stats["decode_steps"]
    return run.stats["decode_tokens"] / steps if steps else None

"""Wall time of one admission wave that held decode-ready sequences back,
in ms: the engine's ``prefill_stall_time`` over ``prefill_stall_waves`` in
the window (scheduler)."""


def read(run):
    waves = run.stats.get("prefill_stall_waves")
    if not waves:
        return None
    return 1e3 * run.stats["prefill_stall_time"] / waves

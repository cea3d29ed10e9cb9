"""Share of the traced stretch in which no operation ran on device 0, in %."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy0_s"] / run.trace["window_s"])

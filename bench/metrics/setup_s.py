"""Seconds from process start to the first due request: weights, engine,
warm-up and every compile."""


def read(run):
    return run.setup_s

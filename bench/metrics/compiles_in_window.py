"""Programs lowered inside the window (a compile, or a load from the
persistent cache): should read 0."""


def read(run):
    return run.compiles_in_window

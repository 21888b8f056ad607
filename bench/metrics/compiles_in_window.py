"""Lane pools and tick: XLA backend compiles inside the measured window."""


def read(run):
    return run.compiles

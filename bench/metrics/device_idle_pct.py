"""Device: share of the profiled window in which no operation ran, in %."""
from bench.phases import idle_pct


def read(run):
    return idle_pct(run)

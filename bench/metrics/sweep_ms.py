"""Harvest sweep: mean time from a lane's harvest to its answer (the
tracer's ``sweep`` phase), in ms."""
from bench.phases import phase_mean_ms


def read(run):
    return phase_mean_ms(run, ("sweep",))

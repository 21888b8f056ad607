"""Lane pools and tick: mean time a request spends in a lane (the tracer's
``resident`` phase), in ms."""
from bench.phases import phase_mean_ms


def read(run):
    return phase_mean_ms(run, ("resident",))

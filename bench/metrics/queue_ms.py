"""Scheduler: mean wait before a lane takes a request (the tracer's
``queued`` and ``pool_queue`` phases), in ms."""
from bench.phases import phase_mean_ms


def read(run):
    return phase_mean_ms(run, ("queued", "pool_queue"))

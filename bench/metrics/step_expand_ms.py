"""Diffusion programs: the part of one step run's device time spent in ops
of the ``expand`` round phase (``step_device_ms`` times that phase's share
of the step executables' op self time), in ms."""
from bench.program import step_phase_ms


def read(run):
    return step_phase_ms(run, "expand")

"""Lane pools and tick: mean duration of the PR-Nibble pools' ``tick``
spans that started in the window, in ms."""
from bench.program import tick_ms


def read(run):
    return tick_ms(run, "pr_nibble")

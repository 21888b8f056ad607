"""Per-request phase times from the program's tracer, for metric readers."""
from __future__ import annotations


def phase_mean_ms(run, names) -> "float | None":
    """Mean over the window's answered requests of the time spent in the
    tracer phases ``names``; None in a run without traces."""
    traces = [r.trace for r in run.records
              if r.trace is not None and r.result is not None]
    if not traces:
        return None
    return sum(sum(t.phase_ms.get(n, 0.0) for n in names)
               for t in traces) / len(traces)


def idle_pct(run) -> "float | None":
    """Share of the profiled window in which no device operation ran."""
    p = run.profile
    if p is None or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - p.busy_s / p.window_s)

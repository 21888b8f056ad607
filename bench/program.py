"""The program's own records, for metric readers: the tracer's ``tick``
spans and the serving layer's op → round-phase table.

A reader returns None where the program keeps no such record: an untraced
run, or a program without the table or without the tick counters."""
from __future__ import annotations


def _tracer(run):
    """The program's tracer, reached through the window's request traces."""
    for rec in run.records:
        if rec.trace is not None:
            return rec.trace.tracer
    return None


def window_ticks(run, method: "str | None" = None) -> list:
    """Finished ``tick`` spans that started inside the window, of the pools
    of ``method`` (every pool when None)."""
    tracer = _tracer(run)
    if tracer is None:
        return []
    close = run.t0 + run.seconds
    prefix = "" if method is None else f"{method}:"
    return [s for s in tracer.spans(include_open=False)
            if s.name == "tick" and run.t0 <= s.t0 < close
            and str(s.attrs.get("pool", "")).startswith(prefix)]


def tick_ms(run, method: str) -> "float | None":
    """Mean duration of the window's ticks of ``method``'s pools, in ms."""
    ticks = window_ticks(run, method)
    if not ticks:
        return None
    return sum(s.duration_ms for s in ticks) / len(ticks)


def edge_slot_use_pct(run) -> "float | None":
    """100 × Σ edges expanded ÷ Σ edge slots computed, over the window's
    ticks."""
    ticks = [s for s in window_ticks(run) if "edge_slots" in s.attrs]
    slots = sum(s.attrs["edge_slots"] for s in ticks)
    if not slots:
        return None
    return 100.0 * sum(s.attrs["edges"] for s in ticks) / slots


def step_phase_ms(run, phase: str) -> "float | None":
    """The share of the step executables' op self time whose op the
    program's table puts under ``phase``, times the mean device time of one
    step run (``step_device_ms``), in ms."""
    p = run.profile
    if p is None:
        return None
    try:
        from repro.serve.aot import op_scopes
    except ImportError:             # a program without the table
        return None
    table = op_scopes()
    seconds, runs = p.module_time("jit_step")
    ops = {k: v for k, v in p.ops_s.items() if k.startswith("jit_step")}
    total = sum(ops.values())
    if not runs or total <= 0:
        return None
    mine = 0.0
    for key, s in ops.items():
        module, _, op = key.partition("/")
        if table.get(module, {}).get(op) == phase:
            mine += s
    return 1e3 * seconds / runs * mine / total

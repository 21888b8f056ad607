"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

A device plane (``/device:TPU:<i>``) has one event per executable run on
its ``XLA Modules`` line (``jit_step(<fingerprint>)`` …) and one per XLA
operation on its ``XLA Ops`` line, nested: a ``while`` event spans the
operations of its body.  The host plane has one line per thread; the
serving thread's line holds the program's ``tick:<pool>`` annotations and,
nested in them, the profiler's Python function events.  The traced window
runs from the end of ``start_trace`` to the start of ``stop_trace``.

From these:

* busy time — the union of the operation intervals inside the window,
  averaged over the devices;
* per executable — device seconds and runs, by module name;
* per operation — device self time (less the operations nested in it),
  named ``<module>/<operation>``;
* idle gaps — each stretch of the window with no operation running, named
  by the host events open at its middle on the serving thread (the tick
  span and the innermost function) and the executable that ran last before
  it.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TICK = "tick:"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops_s: dict             # "<module>/<op>" -> device self seconds
    modules: dict           # module name -> [device seconds, runs] of the
    #                         runs that started in the window
    gaps: list              # (name, seconds), one per idle stretch

    def module_time(self, prefix: str):
        """(device seconds, runs) of the executables named ``prefix``…"""
        hits = [v for k, v in self.modules.items() if k.startswith(prefix)]
        return sum(v[0] for v in hits), sum(v[1] for v in hits)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = collections.defaultdict(float)
        for name, s in self.gaps:
            gaps[name] += s
        idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _module_name(name: str) -> str:
    return name.split("(")[0].strip()


def _op_name(name: str) -> str:
    return name.split(" = ")[0].strip()


def _self_times(events):
    """(start, self ns, name) of nested events: each event's duration less
    that of the events directly inside it."""
    out, stack = [], []          # stack of [end, start, name, self]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            end, start, name, own = stack.pop()
            out.append((start, own, name))
        if stack:
            stack[-1][3] -= e.duration_ns
        stack.append([e.end_ns, e.start_ns, e.name, e.duration_ns])
    out += [(start, own, name) for _, start, name, own in stack]
    return out


def _window(host_lines):
    """(start, end) ns of the traced window from the host's profiler
    calls, or None."""
    start = end = None
    for events in host_lines:
        for e in events:
            if e.name.endswith(" start_trace"):
                start = e.end_ns
            elif e.name.endswith(" stop_trace"):
                end = e.start_ns
    return (start, end) if start is not None and end is not None else None


def reduce_planes(planes) -> Reduced:
    """Reduce the planes of one trace."""
    devices, host_lines = [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append((lines.get(OPS_LINE, []),
                            lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            host_lines += [list(ln.events) for ln in plane.lines]
    serving = max(host_lines, default=[],
                  key=lambda evs: sum(e.name.startswith(TICK) for e in evs))
    window = _window(host_lines)
    every = [e for ops, _ in devices for e in ops]
    if window is None:
        if not every:
            return Reduced(0.0, 0.0, {}, {}, [])
        window = (min(e.start_ns for e in every), max(e.end_ns for e in every))
    w0, w1 = window
    busy = 0.0
    ops_s = collections.defaultdict(float)
    modules = collections.defaultdict(lambda: [0.0, 0])
    gaps = []
    host = sorted((e.start_ns, e.end_ns, e.name) for e in serving)
    for ops, mods in devices:
        inside = [e for e in ops if e.end_ns > w0 and e.start_ns < w1]
        spans = _union((max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in inside)
        busy += sum(b - a for a, b in spans) / 1e9
        runs = sorted((e.start_ns, e.end_ns, _module_name(e.name))
                      for e in mods if e.end_ns > w0 and e.start_ns < w1)
        for a, b, name in runs:
            if a >= w0:     # runs that start in the window, whole
                modules[name][0] += (b - a) / 1e9
                modules[name][1] += 1
        starts = [r[0] for r in runs]
        for start, own, name in _self_times(inside):
            k = bisect.bisect_right(starts, start) - 1
            mod = runs[k][2] if k >= 0 else "?"
            ops_s[f"{mod}/{_op_name(name)}"] += own / 1e9
        gaps += _name_gaps(spans, w0, w1, runs, host)
    return Reduced((w1 - w0) / 1e9, busy / max(len(devices), 1),
                   dict(ops_s), {k: list(v) for k, v in modules.items()},
                   gaps)


def _innermost(host, starts, t):
    """Names of the tick span and the innermost host event open at ``t``."""
    k = bisect.bisect_right(starts, t) - 1
    inner = tick = None
    while k >= 0 and tick is None:
        s, e, name = host[k]
        if e > t:
            if inner is None:
                inner = name
            if name.startswith(TICK):
                tick = name
        k -= 1
    if tick is None:
        return inner or "no span"
    return tick if inner == tick else f"{tick} > {inner}"


def _name_gaps(spans, w0, w1, runs, host):
    starts = [h[0] for h in host]
    done = sorted((e, name) for _, e, name in runs)
    ends = [d[0] for d in done]
    edges = [w0] + [x for s in spans for x in s] + [w1]
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        name = _innermost(host, starts, (a + b) / 2)
        k = bisect.bisect_right(ends, a) - 1
        if k >= 0:
            name += f" after {done[k][1]}"
        out.append((name, (b - a) / 1e9))
    return out


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the one ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {found}")
    return reduce_file(found[0])

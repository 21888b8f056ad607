"""One run of one cell: build, warm up, drive the window, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the names in ``BENCHMARK.json``:

* ``configs[].file`` — the configuration, whose ``generator`` names
  ``bench/graphs/<generator>.py``;
* ``workloads[].traffic`` — ``bench/traffic/<traffic>.json``, read by
  ``bench/loadgen.py``;
* ``per_layer[].name`` — ``bench/metrics/<name>.py``, whose ``read(run)``
  returns the metric's value, or None where the run has nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import queue
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

import jax

from repro.serve import AsyncClusterEngine, LocalClusterEngine
from repro.serve.scheduler import QueueFull
from repro.serve.tracing import Tracer

from bench import check, loadgen, xplane
from bench.reference import HostGraph

ROOT = Path(__file__).resolve().parent.parent
GRACE_S = 60.0          # how long past the window an answer is waited for
PROFILE_S = 4.0         # length of the profiled stretch of a traced window


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(spec: dict, name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; there are {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def metric_reader(name: str, root: Path = ROOT):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------- graph

def graph_key(seed: int):
    """A PRNG key from the whole of ``seed``, which may pass 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def graph_seed(cell: Cell) -> int:
    """The seed the cell's graph is drawn from: the mix's
    ``population_seed``."""
    return cell.traffic["population_seed"]


def build_graph(config: dict, seed: int):
    """The configuration's graph, generated and built on the device."""
    from bench.graphs import csr
    gen = importlib.import_module(f"bench.graphs.{config['generator']}")
    src, dst, n = gen.generate(graph_key(seed), config)
    graph = csr.build_csr(src, dst, n, config["undirected_edges"])
    jax.block_until_ready(graph.indices)
    return graph


# ----------------------------------------------------------------- driving

class Rec:
    """One request of the window, and when and what it was answered."""

    __slots__ = ("req", "done_at", "result", "refused", "trace")

    def __init__(self, req):
        self.req = req
        self.done_at: Optional[float] = None
        self.result = None
        self.refused = False
        self.trace = None

    def submit(self, srv, on_done=None) -> None:
        try:
            fut = srv.submit(self.req)
        except QueueFull:
            self.refused = True
            return
        self.trace = fut.trace

        def done(f):
            self.result = f.result()
            self.done_at = time.monotonic()
            if on_done is not None:
                on_done(self)
        fut.add_done_callback(done)


class CompileCounter:
    """Counts XLA backend compiles inside its ``with`` block."""

    def __enter__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Profiler(threading.Thread):
    """Profiles ``[start, start + length)`` of the host clock into a
    temporary directory, from a thread of its own."""

    def __init__(self, start: float, length: float):
        super().__init__(name="bench-profiler", daemon=True)
        self.start_at, self.length = start, length
        self.dir = tempfile.TemporaryDirectory(prefix="bench-trace-")

    def run(self):
        time.sleep(max(0.0, self.start_at - time.monotonic()))
        jax.profiler.start_trace(self.dir.name)
        time.sleep(max(0.0, self.start_at + self.length - time.monotonic()))
        jax.profiler.stop_trace()


def drive_closed(srv, stream, seconds: float, t0: float,
                 outstanding: int) -> list:
    """Keep ``outstanding`` requests in flight until the window closes."""
    done: "queue.Queue[Rec]" = queue.Queue()
    todo = iter(stream.window)
    recs = []

    def send():
        req = next(todo, None)
        if req is None:
            raise RuntimeError("closed loop ran out of requests: raise "
                               "the mix's population")
        rec = Rec(req)
        recs.append(rec)
        rec.submit(srv, on_done=done.put)
        if rec.refused:
            done.put(rec)

    for _ in range(outstanding):
        send()
    while True:
        left = t0 + seconds - time.monotonic()
        if left <= 0:
            break
        try:
            done.get(timeout=left)
        except queue.Empty:
            break
        send()
    return recs


def wait_for(recs: list, until: float) -> None:
    """Wait, at most until ``until``, for every admitted request."""
    for rec in recs:
        while (not rec.refused and rec.done_at is None
               and time.monotonic() < until):
            time.sleep(0.01)


# ----------------------------------------------------------------- the run

@dataclasses.dataclass
class Run:
    """What a run read, for the metric readers and the report."""
    cell: Cell
    seconds: float
    records: list
    t0: float
    setup_s: float
    resident_bytes: int     # device memory in use when the window opened
    compiles: int
    profile: Optional[xplane.Reduced]


def _warm(srv, eng, traffic: dict, stream) -> None:
    """Compile the pools of every request kind over the buckets the mix
    names (``warm_buckets``, by method), then serve the warm requests."""
    for kind in traffic["mix"]:
        eng.warmup([loadgen.request(kind, 0)],
                   max_bucket=traffic["warm_buckets"][kind["method"]])
    futs = [srv.submit(r) for r in stream.warm]
    for f in futs:
        f.result(timeout=600)


def serve(cell: Cell, graph, seed: int, seconds: float, trace: bool,
          t_start: float) -> Run:
    """Set up the engine on ``graph``, warm it, drive the window, and wait
    up to ``GRACE_S`` past its close for every request it sent."""
    stream = loadgen.make_stream(cell.traffic, np.asarray(graph.deg), seed)
    tracer = Tracer(device_annotations=True) if trace else None
    eng = LocalClusterEngine(graph)
    srv = AsyncClusterEngine(eng, tracer=tracer)
    srv.serve_forever()
    try:
        _warm(srv, eng, cell.traffic, stream)
        resident = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                       for d in jax.devices())
        t0 = time.monotonic()
        setup_s = t0 - t_start
        prof = None
        if trace:
            length = min(PROFILE_S, seconds / 2)
            prof = Profiler(t0 + (seconds - length) / 2, length)
            prof.start()
        with CompileCounter() as compiles:
            recs = drive_closed(srv, stream, seconds, t0,
                                cell.traffic["outstanding"])
        wait_for(recs, t0 + seconds + GRACE_S)
        reduced = None
        if prof is not None:
            prof.join()
            reduced = xplane.reduce_dir(prof.dir.name)
            prof.dir.cleanup()
    finally:
        srv.shutdown(wait=False)
    return Run(cell, seconds, recs, t0, setup_s, int(resident),
               compiles.count, reduced)


def end_to_end(run: Run) -> dict:
    """Every end-to-end metric this run can give, by name."""
    close = run.t0 + run.seconds
    done = sum(1 for r in run.records
               if r.done_at is not None and r.done_at <= close)
    return {"setup_s": run.setup_s, "seeds_per_s": done / run.seconds}


def device_info(trace: Optional[xplane.Reduced]) -> dict:
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}
    if trace is not None:
        info["busy_s"] = trace.busy_s
        info["window_s"] = trace.window_s
    return info


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One whole run; returns the result object the last line prints."""
    graph = build_graph(cell.config, graph_seed(cell))
    run = serve(cell, graph, seed, seconds, trace, t_start)
    device = device_info(run.profile)
    host = HostGraph.of(graph)
    del graph
    verdict = check.judge(run.records, host, cell.traffic, seed)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(run)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": verdict.correct, "attempted": len(run.records),
              "failed": verdict.failed, "metrics": metrics, "device": device}
    if trace and run.profile is not None:
        result["breakdown"] = run.profile.breakdown()
    result["resident_bytes"] = run.resident_bytes
    result["checks"] = verdict.checks
    return result

"""Serving-latency benchmark: deadline scheduler under a Poisson stream.

The serving claim is different from the throughput claims of
`batched_bench.py`: here requests *arrive over time* (Poisson process), each
with a latency budget, and the metric is the request-latency distribution —
p50/p95/p99 — plus the deadline-miss rate, per lane backend (dense vs
sparse).  The `AsyncClusterEngine` runs in its background drive thread while
this process plays an open-loop arrival schedule at it, the standard
serving-benchmark shape.

Warmup is measured *separately* from steady state: each lane first runs
``LocalClusterEngine.warmup`` (AOT-compiling every tick executable the
stream can touch) plus one priming request, reported as the lane's
``warmup_ms`` (and its own ``*_warmup`` CSV row) — the timed Poisson stream
then measures pure serving behavior, never compile time.

The seed mix is serving-shaped: a hot set of repeated seeds (70% of
arrivals) over a uniform cold tail — hot queries repeat in real streams,
which is exactly what the engine's versioned seed→result cache exploits;
the artifact reports the resulting ``cache_hit_rate`` alongside the latency
distribution.

``--characterize`` runs a deterministic no-deadline sweep instead and
writes ``benchmarks/baselines/tick_costs.json`` — measured per-pool tick
costs that seed the EDF planner's cost model (its cold-start fix: without
it a never-ticked pool is costed by a guess exactly when deadlines are
tightest).  The normal benchmark auto-loads that file when present.

Emits the usual `name,us_per_call,derived` CSV rows (us = p50 latency) and
returns a JSON-able dict that `benchmarks/run.py` writes to
``BENCH_serve.json`` — the artifact CI uploads so the serving-latency
trajectory accumulates across PRs.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.serve import (AsyncClusterEngine, ClusterRequest,
                         LocalClusterEngine, MetricsRegistry)
from repro.serve.telemetry import pool_label
from .common import get_graph, emit

TICK_COSTS_SCHEMA = "repro.bench.tick_costs/v1"
TICK_COSTS_PATH = os.path.join(os.path.dirname(__file__), "baselines",
                               "tick_costs.json")


def _percentiles(lat_ms):
    lat = np.sort(np.asarray(lat_ms, np.float64))
    pick = lambda q: float(lat[min(len(lat) - 1,
                                   int(round(q / 100 * (len(lat) - 1))))])
    return dict(p50_ms=pick(50), p95_ms=pick(95), p99_ms=pick(99))


def _request_stream(graph, rng, n_requests: int, hot_seeds: int = 16,
                    hot_fraction: float = 0.75,
                    alphas: tuple = (0.05, 0.02)):
    """Serving-shaped request mix: ``hot_fraction`` of arrivals draw their
    seed from a small hot set (repeated queries — the result cache's
    regime), the rest uniformly from every non-isolated vertex.  α is a
    deterministic function of the seed so a hot seed's repeats share one
    cache identity — real streams re-ask the *same* query, they don't
    re-roll its knobs."""
    cand = np.flatnonzero(np.asarray(graph.deg) > 0)
    hot = rng.choice(cand, size=min(hot_seeds, len(cand)), replace=False)
    seeds = np.where(rng.random(n_requests) < hot_fraction,
                     rng.choice(hot, size=n_requests),
                     rng.choice(cand, size=n_requests)).astype(np.int64)
    return [ClusterRequest(seed=int(s),
                           alpha=float(alphas[int(s) % len(alphas)]),
                           eps=1e-4)
            for s in seeds]


def _run_lane(graph, backend: str, n_requests: int, mean_gap_s: float,
              deadline_ms: float, batch_slots: int, caps: dict,
              seed: int = 0, cost_table=None,
              stream_kw: dict = None) -> dict:
    """Play one Poisson-arrival stream at a fresh scheduler; returns the
    latency/miss summary for the BENCH_serve.json artifact."""
    rng = np.random.default_rng(seed)
    reqs = _request_stream(graph, rng, n_requests, **(stream_kw or {}))
    gaps = rng.exponential(mean_gap_s, size=n_requests)
    engine = LocalClusterEngine(graph, batch_slots=batch_slots,
                                backend=backend, **caps)
    # Warmup, measured apart from the stream: AOT-compile the tick
    # executables of buckets 0..1 (every shape this stream promotes into),
    # then prime each pool with one untimed request so the first *tick*
    # (pool/state allocation, dist jits) is also off the clock.
    t0 = time.perf_counter()
    engine.warmup([ClusterRequest(seed=0, alpha=0.05, eps=1e-4)],
                  max_bucket=1)
    sched = AsyncClusterEngine(engine, max_queue=4 * n_requests,
                               telemetry=MetricsRegistry(),
                               cost_table=cost_table)
    with sched:
        sched.submit(ClusterRequest(seed=int(reqs[0].seed), alpha=0.05,
                                    eps=1e-4)).result(timeout=300.0)
        warmup_ms = (time.perf_counter() - t0) * 1e3
        # scheduler-level hits resolve through engine.cached_result, so the
        # engine counter already covers both the pre-admission fast path
        # and hits discovered at admission time
        hits0 = engine.stats["result_cache_hits"]
        t0 = time.perf_counter()
        futs = []
        for req, gap in zip(reqs, gaps):
            time.sleep(float(gap))      # open-loop: arrivals don't wait
            futs.append(sched.submit(req, deadline_ms=deadline_ms))
        results = [f.result(timeout=300.0) for f in futs]
        wall_s = time.perf_counter() - t0
        hits = engine.stats["result_cache_hits"] - hits0
    lat_ms = [f.latency_ms for f in futs]
    missed = sum(r.deadline_missed for r in results)
    out = _percentiles(lat_ms)
    out.update(
        deadline_miss_rate=missed / n_requests,
        n_requests=n_requests,
        deadline_ms=deadline_ms,
        mean_gap_ms=mean_gap_s * 1e3,
        wall_s=wall_s,
        throughput_rps=n_requests / wall_s,
        backend=backend,
        warmup_ms=warmup_ms,
        aot_compiles=engine.stats["aot_compiles"],
        aot_compile_s=engine.stats["aot_compile_s"],
        cache_hit_rate=hits / n_requests,
        status_syncs=engine.stats["status_syncs"],
    )
    return out


def _smoke_config() -> dict:
    """The CI tier: 256 Poisson requests against the planted SBM, sized so
    warm steady-state ticks are tens of ms (narrow batch, small
    workspaces, 8-round ticks keep per-request latency ≈ iters × per-round
    cost) and the p99 clears the 1 s deadline.  The sparse lane serves the
    α=0.05 slice only — its per-round cost is ~3× dense, so the deep
    α=0.02 walks (83 iterations) belong to the dense lane."""
    return dict(
        name="sbm-planted", n_requests=256, mean_gap_s=0.07,
        deadline_ms=1000.0, batch_slots=4,
        caps=dict(cap_f=1 << 9, cap_e=1 << 12, cap_n=1 << 10,
                  sweep_cap_e=1 << 13, cap_v=1 << 10, rounds_per_step=8),
        lane_streams=dict(
            dense=dict(alphas=(0.05, 0.02), hot_fraction=0.85),
            sparse=dict(alphas=(0.05,), hot_fraction=0.85)))


def _full_config() -> dict:
    return dict(
        name="randLocal-50k", n_requests=64, mean_gap_s=0.005,
        deadline_ms=250.0, batch_slots=8, caps={})


def characterize(smoke: bool = False,
                 path: str = TICK_COSTS_PATH) -> dict:
    """Measure steady-state tick cost per pool (deterministic, no deadlines,
    no Poisson) and write the ``tick_costs.json`` baseline the EDF planner
    seeds its cost model from.  Entries: exact pool labels, plus the
    ``"method:backend"`` family averages the planner falls back to for
    never-characterized buckets."""
    cfg = _smoke_config() if smoke else _full_config()
    graph = get_graph(cfg["name"])
    rng = np.random.default_rng(11)
    entries: dict = {}
    families: dict = {}
    for backend in ("dense", "sparse"):
        engine = LocalClusterEngine(graph, batch_slots=cfg["batch_slots"],
                                    backend=backend, lru_pools=16,
                                    **cfg["caps"])
        engine.warmup([ClusterRequest(seed=0, alpha=0.05, eps=1e-4)],
                      max_bucket=1)
        engine.run(_request_stream(graph, rng, 24))
        for key, pool in engine.pools.items():
            if pool.cost_ema is None:
                continue
            entries[pool_label(key)] = pool.cost_ema
            families.setdefault(f"{key[0]}:{key[1]}", []).append(
                pool.cost_ema)
    for fam, costs in families.items():
        entries[fam] = sum(costs) / len(costs)
    doc = dict(schema=TICK_COSTS_SCHEMA, graph=cfg["name"],
               smoke=smoke, generated_unix=time.time(),
               rounds_per_step=cfg["caps"].get("rounds_per_step", 16),
               entries=entries)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"wrote {path} ({len(entries)} entries)", flush=True)
    return doc


def run(smoke: bool = False, requests: int = None) -> dict:
    cfg = _smoke_config() if smoke else _full_config()
    if requests is not None:
        cfg["n_requests"] = requests
    graph = get_graph(cfg["name"])
    cost_table = TICK_COSTS_PATH if os.path.exists(TICK_COSTS_PATH) else None
    artifact = dict(graph=cfg["name"], smoke=smoke, lanes={})
    for backend in ("dense", "sparse"):
        lane = _run_lane(graph, backend, cfg["n_requests"],
                         cfg["mean_gap_s"], cfg["deadline_ms"],
                         batch_slots=cfg["batch_slots"], caps=cfg["caps"],
                         cost_table=cost_table,
                         stream_kw=cfg.get("lane_streams", {}).get(backend))
        artifact["lanes"][backend] = lane
        emit(f"serve/{cfg['name']}/{backend}_poisson_B={cfg['n_requests']}",
             lane["p50_ms"] * 1e3,
             f"p95_ms={lane['p95_ms']:.1f};p99_ms={lane['p99_ms']:.1f};"
             f"miss_rate={lane['deadline_miss_rate']:.3f};"
             f"rps={lane['throughput_rps']:.1f};"
             f"cache_hit_rate={lane['cache_hit_rate']:.3f}")
        emit(f"serve/{cfg['name']}/{backend}_warmup",
             lane["warmup_ms"] * 1e3,
             f"aot_compiles={lane['aot_compiles']};"
             f"aot_compile_s={lane['aot_compile_s']:.2f}")
    return artifact


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=None,
                    help="override the stream length (default: 256 smoke / "
                         "64 full)")
    ap.add_argument("--characterize", action="store_true",
                    help="measure per-pool tick costs and write "
                         "benchmarks/baselines/tick_costs.json instead of "
                         "running the Poisson benchmark")
    args = ap.parse_args()
    if args.characterize:
        print(json.dumps(characterize(smoke=args.smoke), indent=2))
    else:
        print(json.dumps(run(smoke=args.smoke, requests=args.requests),
                         indent=2))

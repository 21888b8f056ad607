"""Local clustering as a service: many-seed throughput demo.

A burst of mixed-parameter clustering queries (random seeds, α, ε, and a mix
of PR-Nibble and HK-PR) is served four ways:

  1. naive loop — one single-seed jit call per query (the seed repo's path)
  2. batched    — one ``batched_pr_nibble`` dispatch for the PR-Nibble burst
  3. engine     — ``LocalClusterEngine`` continuous batching: fixed lanes,
                  finished slots refilled without recompiling, per-request
                  sweep cuts, overflow promoted through capacity buckets
  4. async      — ``AsyncClusterEngine`` deadline-aware serving: requests
                  submitted with latency budgets from the caller's thread
                  while the scheduler drives in the background (EDF pool
                  ordering), results consumed via future callbacks, and the
                  telemetry registry dumped as JSON at exit

    PYTHONPATH=src python examples/serve_clusters.py [--requests 48]
"""
import argparse
import threading
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import pr_nibble, hk_pr, sweep_cut_dense, batched_pr_nibble
from repro.graphs import rand_local
from repro.serve import AsyncClusterEngine, ClusterRequest, LocalClusterEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--n", type=int, default=20_000)
    ap.add_argument("--batch-slots", type=int, default=16)
    ap.add_argument("--eps", type=float, default=1e-4,
                    help="base truncation threshold (smaller = less local)")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "dense", "sparse"],
                    help="engine lane type; sparse = O(cap_v) state per lane "
                         "(HK-PR requests always serve dense)")
    args = ap.parse_args()
    use_compile_cache()

    print(f"building randLocal graph (n={args.n}) ...")
    g = rand_local(args.n, degree=5, seed=0)
    rng = np.random.default_rng(1)
    seeds = rng.choice(np.flatnonzero(np.asarray(g.deg) > 0),
                       size=args.requests).astype(np.int32)
    reqs = []
    for i, s in enumerate(seeds):
        if i % 4 == 3:
            reqs.append(ClusterRequest(seed=int(s), method="hk_pr",
                                       eps=args.eps, N=10, t=5.0))
        else:
            reqs.append(ClusterRequest(
                seed=int(s), alpha=float(rng.choice([0.1, 0.05])),
                eps=float(rng.choice([args.eps, args.eps / 3]))))

    # 1. naive loop (with per-request sweep, same work as the engine)
    t0 = time.perf_counter()
    naive = []
    for q in reqs:
        if q.method == "pr_nibble":
            res = pr_nibble(g, q.seed, q.eps, q.alpha)
        else:
            res = hk_pr(g, q.seed, N=q.N, eps=q.eps, t=q.t)
        naive.append(sweep_cut_dense(g, res.p, 1 << 11, 1 << 17))
    dt_loop = time.perf_counter() - t0
    print(f"naive loop      : {len(reqs) / dt_loop:7.1f} seeds/s "
          f"({dt_loop * 1e3:.0f} ms total)")

    # 2. one batched dispatch for the PR-Nibble subset (diffusion only)
    prn = [q for q in reqs if q.method == "pr_nibble"]
    t0 = time.perf_counter()
    out = batched_pr_nibble(g, np.asarray([q.seed for q in prn], np.int32),
                            np.asarray([q.eps for q in prn], np.float32),
                            np.asarray([q.alpha for q in prn], np.float32))
    dt_bat = time.perf_counter() - t0
    print(f"batched dispatch: {len(prn) / dt_bat:7.1f} seeds/s "
          f"({len(out.buckets)} capacity bucket(s), PR-Nibble subset)")

    # 3. the serving engine: mixed methods, slot refill, sweep included
    eng = LocalClusterEngine(g, batch_slots=args.batch_slots,
                             backend=args.backend)
    t0 = time.perf_counter()
    results = eng.run(reqs)
    dt_eng = time.perf_counter() - t0
    print(f"cluster engine  : {len(reqs) / dt_eng:7.1f} seeds/s "
          f"({dt_eng * 1e3:.0f} ms total, incl. sweep cuts)")
    s = eng.stats
    print(f"  steps={s['steps']} injections={s['injections']} "
          f"promotions={s['promotions']} pools={s['pools_created']} "
          f"compiled_shapes={len(s['bucket_shapes'])}")

    best = min(results, key=lambda r: r.conductance)
    print(f"\nbest cluster: seed={best.request.seed} size={best.size} "
          f"phi={best.conductance:.4f} ({best.request.method})")
    for r in results[:4]:
        print(f"  seed={r.request.seed:6d} {r.request.method:9s} "
              f"eps={r.request.eps:g} size={r.size:4d} "
              f"phi={r.conductance:.4f} pushes={r.pushes}")

    # 4. deadline-aware async serving: submit with budgets from this thread,
    #    the scheduler ticks in its own; consume via callbacks
    print("\nasync serving (deadline-aware):")
    done = threading.Event()
    hits, misses = [], []

    def on_done(fut):
        r = fut.result()
        (misses if r.deadline_missed else hits).append(fut)
        if len(hits) + len(misses) == len(reqs):
            done.set()

    with AsyncClusterEngine(g, batch_slots=args.batch_slots,
                            max_queue=4 * len(reqs),
                            backend=args.backend) as sched:
        t0 = time.perf_counter()
        for i, q in enumerate(reqs):
            # tight budgets on every 3rd request show the miss path;
            # the rest get a comfortable budget
            fut = sched.submit(q, deadline_ms=25.0 if i % 3 == 0 else 5000.0,
                               priority=1 if i % 3 == 0 else 0)
            fut.add_done_callback(on_done)
        done.wait(timeout=120.0)
        dt = time.perf_counter() - t0
        print(f"async engine    : {len(reqs) / dt:7.1f} seeds/s "
              f"({dt * 1e3:.0f} ms wall, submit-to-callback)")
        lat = sorted(f.latency_ms for f in hits + misses)
        print(f"  p50={lat[len(lat) // 2]:.1f}ms "
              f"p95={lat[int(0.95 * (len(lat) - 1))]:.1f}ms  "
              f"deadline hits={len(hits)} misses={len(misses)} "
              f"(misses return flagged partial harvests, never block)")
        telemetry_json = sched.telemetry.to_json()
    print("telemetry dump (truncated):")
    for line in telemetry_json.splitlines()[:16]:
        print("  " + line)
    print(f"  ... ({len(telemetry_json.splitlines())} lines total)")


if __name__ == "__main__":
    main()

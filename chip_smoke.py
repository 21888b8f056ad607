#!/usr/bin/env python3
"""Serve seed queries through the clustering service on a TPU, and check them.

    python chip_smoke.py              # one chip: the served path + Pallas kernels
    python chip_smoke.py --chips 4    # four chips: the sharded (dist) path only

One chip.  Builds a Graph500 R-MAT graph from ``--seed`` — scale 22 (about
4.2M vertices), edge factor 16, (a, b, c) = (0.57, 0.19, 0.19) — and puts
its CSR on the device.  Then:

* **main** — serves ``--requests`` (at least 64) mixed PR-Nibble and HK-PR
  requests through ``AsyncClusterEngine`` over a ``LocalClusterEngine``
  with default knobs: PR-Nibble on sparse lanes, HK-PR on dense lanes, and
  one request that has to climb the capacity ladder.  Every answer must
  equal the single-seed driver's at the same capacities bit for bit
  (docs/algorithms.md, guarantees #1-#3); at least four diffusions are
  checked against the numpy references in ``repro.core.seq``.
* **pallas** — compares each Pallas op with XLA at served shapes (integer
  results exact, float results within rtol 1e-5 / atol 1e-6), serves 8 of
  the requests on ``ops_backend="pallas"``, checks that its tick programs
  hold the kernels (``tpu_custom_call``) and the default ones do not, and
  counts how many answers equal the ``xla`` answers bit for bit.

Four chips.  Partitions the same graph over a 4-device ``data`` mesh and
serves PR-Nibble requests on ``dist`` lanes; every answer must equal the
one-chip dense engine's bit for bit (guarantee #7).

Exits nonzero before building anything when JAX finds no TPU, and on any
mismatch or error.  Earlier lines are ``key=value`` reports; the last line
is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compat import make_mesh  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import (hk_pr, ops, pr_nibble, pr_nibble_sparse,  # noqa: E402
                        seq, sweep_cut_dense, sweep_cut_sparse)
from repro.core.batched_sparse import pick_backend  # noqa: E402
from repro.core.pr_nibble_sparse import pr_nibble_sparse_fixedcap  # noqa: E402
from repro.graphs import GraphHandle, rmat  # noqa: E402
from repro.kernels import ops as kops, ref  # noqa: E402
from repro.serve import (AsyncClusterEngine, ClusterRequest,  # noqa: E402
                         LocalClusterEngine)

GRAPH500 = dict(a=0.57, b=0.19, c=0.19, edge_factor=16)
KERNEL = "tpu_custom_call"
RTOL, ATOL = 1e-5, 1e-6          # f32 agreement of the pallas ops with xla


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


class Mismatch(AssertionError):
    """An answer that differs from what it is checked against."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Mismatch(what)


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


# ------------------------------------------------------------------- graph

def build_graph(scale: int, seed: int):
    """Graph500 R-MAT at ``scale``, CSR on the default device."""
    return rmat(scale, seed=seed, **GRAPH500)


# ---------------------------------------------------------------- requests

def find_promotion(graph, eng, limit: int = 32):
    """A PR-Nibble request on sparse lanes that overflows bucket 0 of
    ``eng``'s ladder and fits bucket 1, from a hub whose degree lies around
    bucket 0's value capacity — or None."""
    deg = np.asarray(graph.deg)
    n = graph.n
    caps = [dict(cap_f=min(eng.cap_f << b, n + 1), cap_e=eng.cap_e << b,
                 cap_v=min(eng.cap_v << b, n + 1)) for b in (0, 1)]
    hubs = np.flatnonzero((deg > caps[0]["cap_v"] // 2)
                          & (deg < caps[1]["cap_v"]))
    hubs = hubs[np.argsort(deg[hubs])][::-1][:limit]
    for alpha, eps in ((0.1, 1e-4), (0.05, 1e-4), (0.1, 3e-5)):
        for s in hubs:
            over = [bool(pr_nibble_sparse_fixedcap(
                graph, int(s), eps, alpha, True, c["cap_f"], c["cap_e"],
                c["cap_v"]).overflow) for c in caps]
            if over == [True, False]:
                return ClusterRequest(seed=int(s), alpha=alpha, eps=eps)
    return None


def make_requests(graph, eng, count: int, seed: int):
    """``count`` distinct-seed requests: a quarter HK-PR (dense lanes), the
    rest PR-Nibble (the lane ``auto`` picks), one of them a ladder climber
    when the graph has one."""
    rng = np.random.default_rng(seed)
    deg = np.asarray(graph.deg)
    pool = np.flatnonzero((deg >= 2) & (deg <= 64))
    seeds = rng.choice(pool, size=count, replace=False)
    reqs = []
    for i, s in enumerate(seeds):
        if i % 4 == 3:
            reqs.append(ClusterRequest(seed=int(s), method="hk_pr",
                                       eps=float(rng.choice([1e-4, 3e-4])),
                                       N=10, t=5.0))
        else:
            reqs.append(ClusterRequest(seed=int(s),
                                       alpha=float(rng.choice([0.05, 0.1])),
                                       eps=float(rng.choice([1e-4, 3e-5]))))
    if pick_backend(graph.n, eng.cap_v, eng.sparse_ratio) == "sparse":
        climber = find_promotion(graph, eng)
        if climber is not None and climber.seed not in seeds:
            reqs[0] = climber
    return reqs


# --------------------------------------------------------------- reference

def reference(graph, eng, req, lane: str, bucket: int):
    """What the single-seed drivers answer for ``req`` at ``eng``'s
    capacities: the bucketed diffusion, then the sweep at the capacities of
    ``bucket`` (doubling as the engine's harvest does when its workspace
    overflows).  Returns ``(answer, p)``, ``p`` the diffusion as the driver
    returned it."""
    n = graph.n
    max_cap_e = eng.cap_e << eng.max_bucket
    max_se = eng.sweep_cap_e << eng.max_bucket
    cap_se = eng.sweep_cap_e << bucket
    if lane == "sparse":
        d = pr_nibble_sparse(graph, req.seed, req.eps, req.alpha,
                             req.optimized, cap_f=eng.cap_f, cap_e=eng.cap_e,
                             cap_v=eng.cap_v, max_cap_e=max_cap_e)
        while True:
            sw = sweep_cut_sparse(graph, d.p.ids, d.p.vals, d.p.count, cap_se)
            if not bool(sw.overflow) or cap_se >= max_se:
                break
            cap_se = min(cap_se * 2, max_se)
    else:
        if req.method == "hk_pr":
            d = hk_pr(graph, req.seed, N=req.N, eps=req.eps, t=req.t,
                      cap_f=eng.cap_f, cap_e=eng.cap_e, max_cap_e=max_cap_e)
        else:
            d = pr_nibble(graph, req.seed, req.eps, req.alpha, req.optimized,
                          cap_f=eng.cap_f, cap_e=eng.cap_e,
                          max_cap_e=max_cap_e, beta=req.beta)
        cap_n = min(eng.cap_n << bucket, n)
        while True:
            sw = sweep_cut_dense(graph, d.p, cap_n, cap_se)
            if not bool(sw.overflow) or (cap_n >= n and cap_se >= max_se):
                break
            cap_n, cap_se = min(cap_n * 2, n), min(cap_se * 2, max_se)
    size = int(sw.best_size)
    answer = dict(conductance=np.asarray(sw.best_conductance, np.float32),
                  size=size, volume=int(sw.best_volume), support=int(sw.nnz),
                  pushes=int(d.pushes), iterations=int(d.iterations),
                  cluster=np.asarray(sw.order)[:size].astype(np.int32))
    return answer, d.p


def answer_of(res) -> dict:
    return dict(conductance=np.asarray(res.conductance, np.float32),
                size=res.size, volume=res.volume, support=res.support,
                pushes=res.pushes, iterations=res.iterations,
                cluster=np.asarray(res.cluster, np.int32))


def same_answer(a: dict, b: dict) -> bool:
    """Bit for bit: every counter, the conductance's bits, the members."""
    return (all(a[k] == b[k] for k in ("size", "volume", "support", "pushes",
                                       "iterations"))
            and a["conductance"].tobytes() == b["conductance"].tobytes()
            and np.array_equal(a["cluster"], b["cluster"]))


# ------------------------------------------------------------------ phases

def serve(eng, reqs):
    """Serve ``reqs`` through an AsyncClusterEngine over ``eng``; results in
    request order."""
    srv = AsyncClusterEngine(eng)
    srv.serve_forever()
    futures = [srv.submit(r) for r in reqs]
    results = [f.result(timeout=1800) for f in futures]
    srv.shutdown()
    return results


def tick_hlo(eng, req, bucket: int = 0) -> str:
    """The compiled tick (step) program of the pool serving ``req``."""
    return eng._executables_for(eng._pool_key(req, bucket)).step.as_text()


def main_phase(graph, count: int, seed: int, engine_kw=None):
    """Serve ``count`` requests on the default path; check every answer
    against the single-seed drivers.  Returns (engine, requests, results)."""
    eng = LocalClusterEngine(graph, **(engine_kw or {}))
    reqs = make_requests(graph, eng, count, seed)
    t0 = time.perf_counter()
    protos = {}
    for r in reqs:
        protos.setdefault((r.method, eng._resolve_backend(r)), r)
    warm = eng.warmup(list(protos.values()), max_bucket=1)
    t_warm = time.perf_counter() - t0

    t0 = time.perf_counter()
    with CompileCounter() as compiles:
        results = serve(eng, reqs)
    t_serve = time.perf_counter() - t0
    lanes = sorted({r.backend for r in results})
    log(phase="main", requests_served=len(results), lanes=",".join(lanes),
        methods=",".join(sorted({r.request.method for r in results})),
        promotions=eng.stats["promotions"],
        compiles_after_warmup=compiles.count,
        warmup_compiled=warm["compiled"], warmup_seconds=round(t_warm, 3),
        serve_seconds=round(t_serve, 3))
    check(len(results) == count, "not every request was answered")
    check(not any(r.deadline_missed or r.overflow for r in results),
          "a request missed its deadline or overflowed every bucket")
    check(eng.stats["promotions"] >= 1, "no request climbed the ladder")
    check({"dense", "sparse"} <= set(lanes), "not both lane types ran")

    t0 = time.perf_counter()
    for req, res in zip(reqs, results):
        want, _ = reference(graph, eng, req, res.backend, res.bucket)
        check(same_answer(answer_of(res), want),
              f"seed {req.seed} ({req.method}, {res.backend}, bucket "
              f"{res.bucket}): engine {answer_of(res)} != driver {want}")
    log(phase="main_check", bit_identical_to_single_seed=len(results),
        seconds=round(time.perf_counter() - t0, 3))
    return eng, reqs, results


def seq_phase(graph, eng, reqs, results, per_method: int = 2):
    """Check diffusions against the numpy references, with the tolerances of
    tests/test_diffusions.py: the ``per_method`` smallest of each method,
    the ladder climber (``reqs[0]``, when it was promoted) and one more
    request answered above bucket 0."""
    t0 = time.perf_counter()
    host = graph.to_numpy()
    n = graph.n
    picks = []
    for method in ("pr_nibble", "hk_pr"):
        picks += [i for _, i in sorted(
            (res.support, i) for i, res in enumerate(results)
            if res.request.method == method)[:per_method]]
    promoted = [i for i, res in enumerate(results) if res.bucket > 0]
    picks += [i for i in promoted if i not in picks and i == 0]   # climber
    picks += [i for i in promoted if i not in picks][:1]
    checked = 0
    for i in picks:
        req, res = reqs[i], results[i]
        method = req.method
        _, p = reference(graph, eng, req, res.backend, res.bucket)
        if res.backend == "sparse":
            dense = np.zeros(n, np.float64)
            k = int(p.count)
            dense[np.asarray(p.ids)[:k]] = np.asarray(p.vals)[:k]
        else:
            dense = np.asarray(p, np.float64)
        if method == "pr_nibble":
            ref = seq.seq_pr_nibble(host, req.seed, req.eps, req.alpha,
                                    req.optimized)
        else:
            ref = seq.seq_hk_pr(host, req.seed, req.N, req.eps, req.t)
        want = np.zeros(n, np.float64)
        want[list(ref["p"])] = list(ref["p"].values())
        if method == "pr_nibble":
            corr = float(np.corrcoef(dense, want)[0, 1])
            check(corr > 0.9999, f"seed {req.seed}: corr {corr}")
        else:
            check(np.allclose(dense, want, rtol=1e-3,
                              atol=1e-5 * want.max()),
                  f"seed {req.seed}: HK-PR differs from seq_hk_pr")
        checked += 1
    log(phase="seq_check", checked_against_numpy=checked,
        promoted_checked=sum(i in promoted for i in picks),
        seconds=round(time.perf_counter() - t0, 3))
    check(checked >= 4, "fewer than 4 answers checked against numpy")


def pallas_ops_phase(graph, eng, seed: int):
    """Each op on ``pallas`` against ``xla`` at served shapes; for the
    scatters, each backend also against the host left fold in submission
    order, which ``pallas`` must equal bit for bit.  Returns
    ``{op: bit_identical}``."""
    rng = np.random.default_rng(seed)
    n, m = graph.n, eng.cap_e
    hot = rng.integers(0, n, 2048)
    idx = np.where(rng.random(m) < 0.5, rng.choice(hot, m),
                   rng.integers(0, n, m)).astype(np.int32)
    cases = {
        "scatter_add_f32": (ops.scatter_add, (
            jnp.asarray(rng.random(n, np.float32)), jnp.asarray(idx),
            jnp.asarray(rng.random(m, np.float32) - 0.3),
            jnp.asarray(rng.random(m) < 0.9)), {}),
        # the dense sweep's shape in f32: one destination group takes all
        # 131,072 contributions
        "scatter_add_f32_one_group": (ops.scatter_add, (
            jnp.asarray(rng.random(eng.cap_n + 2, np.float32)),
            jnp.asarray(rng.integers(0, eng.cap_n + 2, eng.sweep_cap_e),
                        jnp.int32),
            jnp.asarray(rng.random(eng.sweep_cap_e, np.float32) - 0.3),
            jnp.asarray(rng.random(eng.sweep_cap_e) < 0.9)), {}),
        "scatter_add_i32": (ops.scatter_add, (
            jnp.zeros(eng.cap_n + 2, jnp.int32),
            jnp.asarray(rng.integers(0, eng.cap_n + 2, eng.sweep_cap_e),
                        jnp.int32),
            jnp.asarray(rng.choice([-1, 1], eng.sweep_cap_e), jnp.int32),
            jnp.asarray(rng.random(eng.sweep_cap_e) < 0.9)), {}),
        "segment_merge": (ops.segment_merge, (
            jnp.asarray(np.where(rng.random(eng.cap_v + m) < 0.1, n,
                                 rng.choice(hot, eng.cap_v + m)), jnp.int32),
            jnp.asarray(rng.random(eng.cap_v + m, np.float32)),
            n, eng.cap_v), {}),
        "prefix_sum_i32": (ops.prefix_sum, (
            jnp.asarray(rng.integers(0, 64, m), jnp.int32),), {}),
        "prefix_sum_f32": (ops.prefix_sum, (
            jnp.asarray(rng.random(eng.cap_n, np.float32)),), {}),
    }
    found = {}
    t0 = time.perf_counter()
    for name, (op, args, kw) in cases.items():
        outs = {}
        for backend in ("xla", "pallas"):
            fn = jax.jit(lambda *a, _op=op, _b=backend: _op(*a, backend=_b),
                         static_argnums=tuple(i for i, a in enumerate(args)
                                              if isinstance(a, int)))
            out = fn(*args)
            outs[backend] = [np.asarray(o) for o in
                             (out if isinstance(out, tuple) else (out,))]
        same = all(a.tobytes() == b.tobytes()
                   for a, b in zip(outs["xla"], outs["pallas"]))
        for a, b in zip(outs["xla"], outs["pallas"]):
            if np.issubdtype(a.dtype, np.integer):
                check(np.array_equal(a, b), f"{name}: integer result differs")
            else:
                check(np.allclose(b, a, rtol=RTOL, atol=ATOL),
                      f"{name}: max |pallas - xla| = "
                      f"{float(np.max(np.abs(b - a)))}")
        err = max(float(np.max(np.abs(b.astype(np.float64) - a)))
                  for a, b in zip(outs["xla"], outs["pallas"]) if a.size)
        found[name] = same
        folds = {}
        if op is ops.scatter_add:
            # which backend computes the left fold in submission order
            fold = ref.scatter_add_ref(*(np.asarray(a) for a in args))
            folds = {f"{b}_is_left_fold": outs[b][0].tobytes() == fold.tobytes()
                     for b in ("xla", "pallas")}
            check(folds["pallas_is_left_fold"],
                  f"{name}: pallas is not the left fold in submission order")
        log(phase="pallas_op", op=name, bit_identical=same, max_abs_diff=err,
            **folds)
    log(phase="pallas_ops", seconds=round(time.perf_counter() - t0, 3))
    return found


def pallas_serve_phase(graph, xla_eng, reqs, results, count: int = 8):
    """Serve ``count`` of the answered requests — the lowest buckets, the
    methods alternating — on ``ops_backend="pallas"``; returns how many
    answers equal the xla answers bit for bit.  Compiled kernels show in the
    tick programs as ``tpu_custom_call``; interpreted ones do not."""
    by_method = {}
    for _, i in sorted((res.bucket, i) for i, res in enumerate(results)):
        by_method.setdefault(reqs[i].method, []).append((reqs[i], results[i]))
    picks = []
    while len(picks) < count and any(by_method.values()):
        for method in sorted(by_method):
            if by_method[method] and len(picks) < count:
                picks.append(by_method[method].pop(0))
    eng = LocalClusterEngine(graph, ops_backend="pallas",
                             **{k: getattr(xla_eng, k) for k in
                                ("cap_f", "cap_e", "cap_n", "sweep_cap_e",
                                 "cap_v")})
    t0 = time.perf_counter()
    out = serve(eng, [req for req, _ in picks])
    equal = sum(same_answer(answer_of(a), answer_of(b))
                for a, (_, b) in zip(out, picks))
    protos = {req.method: req for req, _ in picks}
    held = [KERNEL in tick_hlo(eng, req) for req in protos.values()]
    held_default = [KERNEL in tick_hlo(xla_eng, req)
                    for req in protos.values()]
    log(phase="pallas_serve", requests_served=len(out),
        bit_identical_to_xla=equal, tick_programs_hold_kernels=all(held),
        default_tick_programs_hold_kernels=any(held_default),
        seconds=round(time.perf_counter() - t0, 3))
    check(all(held) if not kops.interpret() else not any(held),
          "the pallas tick programs do not hold the compiled kernels")
    check(not any(held_default), "a default tick program holds a kernel")
    check(len(out) == count, "pallas engine did not answer every request")
    return equal


def dist_phase(graph, devices, count: int, seed: int, engine_kw=None):
    """PR-Nibble on dist lanes over a ``len(devices)``-device mesh against
    the one-chip dense engine, bit for bit."""
    mesh = make_mesh((len(devices),), ("data",))
    handle = GraphHandle.shard(graph, mesh)
    pg = handle.partitioned()
    placed = sorted(d.id for d in pg.indices.sharding.device_set)
    log(phase="dist_layout", shards=pg.num_shards, rows_per_shard=pg.rows_per,
        slab_device_set=",".join(map(str, placed)))
    check(len(placed) == len(devices), "graph shards are not on every device")
    kw = engine_kw or {}
    dense = LocalClusterEngine(graph, backend="dense", **kw)
    dist = LocalClusterEngine(handle, backend="dist", **kw)
    reqs = [r for r in make_requests(graph, dense, count, seed)
            if r.method == "pr_nibble"]
    t0 = time.perf_counter()
    got = serve(dist, reqs)
    t_dist = time.perf_counter() - t0
    state_devices = sorted({d.id for pool in dist.pools.values()
                            for d in pool.state.p.sharding.device_set})
    t0 = time.perf_counter()
    want = serve(dense, reqs)
    t_dense = time.perf_counter() - t0
    equal = sum(same_answer(answer_of(a), answer_of(b))
                for a, b in zip(got, want))
    log(phase="dist", requests_served=len(got),
        lanes=",".join(sorted({r.backend for r in got})),
        lane_state_device_set=",".join(map(str, state_devices)),
        promotions=dist.stats["promotions"],
        bit_identical_to_one_chip_dense=equal,
        dist_seconds=round(t_dist, 3), dense_seconds=round(t_dense, 3))
    check(len(state_devices) == len(devices), "lane state is not sharded")
    check(equal == len(reqs), f"{len(reqs) - equal} dist answers differ")


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=64)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, "
              f"{len(devices)} found", file=sys.stderr)
        return 2
    check(args.requests >= 64, "serve at least 64 requests")
    log(cache_dir=use_compile_cache())

    t0 = time.perf_counter()
    graph = build_graph(args.scale, args.seed)
    csr_bytes = sum(int(a.nbytes) for a in (graph.indptr, graph.indices,
                                             graph.deg))
    log(phase="graph", family="rmat", scale=args.scale, **GRAPH500,
        n=graph.n, m=graph.m, csr_bytes=csr_bytes,
        seconds=round(time.perf_counter() - t0, 3))

    if args.chips == 4:
        t0 = time.perf_counter()
        dist_phase(graph, devices[:4], args.requests, args.seed)
        log(phase="dist_total", seconds=round(time.perf_counter() - t0, 3))
    else:
        t0 = time.perf_counter()
        eng, reqs, results = main_phase(graph, args.requests, args.seed)
        seq_phase(graph, eng, reqs, results)
        log(phase="main_total", seconds=round(time.perf_counter() - t0, 3))
        t0 = time.perf_counter()
        pallas_ops_phase(graph, eng, args.seed)
        pallas_serve_phase(graph, eng, reqs, results)
        log(phase="pallas_total", seconds=round(time.perf_counter() - t0, 3))

    stats = devices[0].memory_stats() or {}
    log(peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

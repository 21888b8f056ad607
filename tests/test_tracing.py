"""Tracer correctness: concurrency-safe ring buffer, purity (guarantee #8:
tracing never changes answers), full-lifecycle span trees, deadline-miss
postmortems, and the disabled-tracer near-zero-overhead contract."""
import json
import threading
import time

import numpy as np
import pytest

from repro.serve import (AsyncClusterEngine, ClusterRequest,
                         LocalClusterEngine, MetricsRegistry, Tracer)
from repro.serve.tracing import RequestTrace, annotate

ENGINE_CAPS = dict(cap_f=1 << 11, cap_e=1 << 15, cap_n=1 << 10,
                   sweep_cap_e=1 << 15)


def _requests(graph, n, seed=0, **kw):
    rng = np.random.default_rng(seed)
    seeds = rng.choice(np.flatnonzero(np.asarray(graph.deg) > 0), size=n)
    return [ClusterRequest(seed=int(s), alpha=0.05, eps=1e-4, **kw)
            for s in seeds]


def _assert_same_results(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.conductance == rb.conductance
        assert ra.size == rb.size and ra.volume == rb.volume
        assert ra.support == rb.support and ra.pushes == rb.pushes
        assert ra.iterations == rb.iterations and ra.bucket == rb.bucket
        assert np.array_equal(ra.cluster, rb.cluster)


# ------------------------------------------------------------------- purity

def test_engine_traced_bit_identical_to_untraced(sbm_graph):
    """Guarantee #8 at the engine layer: same stream, one flight-recorded."""
    reqs = _requests(sbm_graph, 10)
    traced = LocalClusterEngine(sbm_graph, batch_slots=4, tracer=Tracer(),
                                **ENGINE_CAPS).run(reqs)
    plain = LocalClusterEngine(sbm_graph, batch_slots=4,
                               **ENGINE_CAPS).run(reqs)
    _assert_same_results(traced, plain)


def test_scheduler_traced_bit_identical_and_full_lifecycle(sbm_graph):
    """Guarantee #8 through AsyncClusterEngine, driven deterministically
    (single-threaded tick(), no deadlines), plus the span-tree shape: every
    request's phases tile its root span."""
    reqs = _requests(sbm_graph, 8)
    tracer = Tracer()
    sched = AsyncClusterEngine(sbm_graph, batch_slots=4, tracer=tracer,
                               **ENGINE_CAPS)
    futs = [sched.submit(r) for r in reqs]
    while sched.inflight():
        sched.tick()
    traced = [f.result() for f in futs]
    plain = LocalClusterEngine(sbm_graph, batch_slots=4,
                               **ENGINE_CAPS).run(reqs)
    _assert_same_results(traced, plain)
    for fut in futs:
        rt = fut.trace
        assert rt.status == "resolved"
        # contiguous phases → coverage ~100% of the root span by
        # construction (the ≥95% artifact gate allows clock jitter)
        assert rt.coverage() >= 0.95
        for phase in ("queued", "pool_queue", "resident", "sweep",
                      "deliver"):
            assert phase in rt.phase_ms, phase
        tree = tracer.request_tree(rt.rid)
        assert tree["rid"] == rt.rid and len(tree["tree"]) == 1
        root = tree["tree"][0]
        assert root["name"] == "request"
        assert {c["name"] for c in root["children"]} >= {
            "queued", "pool_queue", "resident", "sweep", "deliver"}


# -------------------------------------------------------------- concurrency

def test_concurrent_emission_never_corrupts_ring():
    """Hammer one small-capacity tracer from many threads: the ring stays
    bounded, counts stay consistent, and every finished span is well-formed."""
    tracer = Tracer(capacity=256)
    n_threads, per_thread = 8, 300
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(per_thread):
            rt = tracer.request(tid=tid)
            rt.phase("queued")
            rt.event("injected", i=i)
            rt.phase("deliver")
            rt.finish("resolved")
            with tracer.span("tick", cat="pool", pool=f"t{tid}") as sid:
                with tracer.scope(parent=sid):
                    annotate("ladder_dispatch", hop=0)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tracer.spans()
    assert len(spans) <= 256 + len(tracer._open)
    assert tracer.dropped > 0          # capacity was genuinely exercised
    sids = [s.sid for s in spans]
    assert len(sids) == len(set(sids))  # no span recorded twice
    for s in spans:
        assert s.t1 is None or s.t1 >= s.t0
    # export stays structurally valid after the stampede
    json.dumps([s.to_dict() for s in spans])


# -------------------------------------------------------------- postmortems

def test_deadline_miss_dumps_postmortem(sbm_graph):
    tm = MetricsRegistry()
    tracer = Tracer()
    sched = AsyncClusterEngine(sbm_graph, batch_slots=2, telemetry=tm,
                               tracer=tracer, **ENGINE_CAPS)
    futs = [sched.submit(r, deadline_ms=0.001)
            for r in _requests(sbm_graph, 4)]
    while sched.inflight():
        sched.tick()
    missed = [f for f in futs if f.result().deadline_missed]
    assert missed, "instant deadlines must miss"
    snap = tm.snapshot()
    assert snap["schema"].startswith("repro.serve.metrics/")
    pms = snap["postmortems"]
    assert len(pms) == len(missed)
    for pm in pms:
        assert pm["tree"]["tree"], "postmortem carries the span tree"
        assert "phases_ms" in pm and pm["deadline_ms"] == 0.001
    json.dumps(snap)                  # snapshot stays JSON-able


def test_postmortems_bounded():
    tm = MetricsRegistry(max_postmortems=3)
    for i in range(10):
        tm.add_postmortem(dict(ticket=i))
    kept = tm.postmortems()
    assert [p["ticket"] for p in kept] == [7, 8, 9]


# ----------------------------------------------------------------- overhead

def test_disabled_tracer_is_near_zero_overhead(sbm_graph):
    """The ambient annotate() hook with no active scope must cost one
    attribute lookup — generous wall bound so CI can't flake."""
    t0 = time.perf_counter()
    for _ in range(100_000):
        annotate("ladder_dispatch", hop=0)
    assert time.perf_counter() - t0 < 0.5
    # engine without a tracer records nothing and allocates no traces
    eng = LocalClusterEngine(sbm_graph, batch_slots=2, **ENGINE_CAPS)
    eng.run(_requests(sbm_graph, 2))
    assert eng._rt == {}


def test_ladder_annotations_reach_active_scope(sbm_graph):
    """The core drivers' ladder_dispatch events land under a tick span when
    a scope is active — threaded up from repro.core.batched with no direct
    core→serve import."""
    from repro.core.batched import batched_pr_nibble
    tracer = Tracer()
    seeds = _requests(sbm_graph, 2)
    with tracer.span("tick", cat="pool") as sid:
        with tracer.scope(parent=sid):
            batched_pr_nibble(sbm_graph, [r.seed for r in seeds],
                              alpha=0.05, eps=1e-4,
                              cap_f=1 << 11, cap_e=1 << 15)
    ann = [s for s in tracer.spans() if s.name == "ladder_dispatch"]
    assert ann, "ladder dispatches must annotate the active scope"
    for s in ann:
        assert s.parent == sid
        assert "bucket" in s.attrs and "lanes" in s.attrs
        assert "pushes" in s.attrs

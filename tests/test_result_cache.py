"""Versioned seed→result cache (serve/result_cache.py).

The contracts under test, matching docs/algorithms.md guarantee #9:

  * hit / miss / graph-version invalidation — a cached community is served
    only at the version it was computed at; bumping the handle's version
    makes every entry stale at once.
  * bit-identity — a cache hit carries exactly the bits a lane would have
    computed (cluster, φ, counters), and hits never corrupt the cached
    entry (copy-on-get).
  * LRU bounding — the cache holds at most ``capacity`` entries, evicting
    least-recently-used; deadline-missed partials are never admitted.
"""
import numpy as np
import pytest

from repro.serve import (ClusterRequest, ClusterResult, LocalClusterEngine,
                         ResultCache, result_key)

CAPS = dict(cap_f=1 << 9, cap_e=1 << 12, cap_n=1 << 10, sweep_cap_e=1 << 13,
            cap_v=1 << 9)


def _result(seed: int, missed: bool = False) -> ClusterResult:
    return ClusterResult(
        request=ClusterRequest(seed=seed), conductance=0.5, size=2,
        volume=4, support=3, cluster=np.array([seed, seed + 1], np.int32),
        pushes=7, iterations=3, bucket=0, overflow=False,
        deadline_missed=missed)


# ------------------------------------------------------------------ key shape

def test_result_key_versions_and_lane_families():
    req = ClusterRequest(seed=5, alpha=0.01, eps=1e-5)
    k_dense = result_key(req, "dense", "xla", graph_version=0)
    # dist lanes produce bit-identical rows to dense lanes (guarantee #7):
    # one cache entry serves both
    assert result_key(req, "dist", "xla", graph_version=0) == k_dense
    # sparse lanes run the sparse update order — separate identity class
    assert result_key(req, "sparse", "xla", graph_version=0) != k_dense
    # the graph version leads the key: any bump is a wholesale invalidation
    assert result_key(req, "dense", "xla", graph_version=1) != k_dense
    # the kernel backend is key material: bit-identity across it is shown
    # on the CPU, not guaranteed on every platform (guarantee #6)
    assert result_key(req, "dense", "pallas", 0) != k_dense
    assert result_key(req, "dense", "xla", 0) == k_dense


def test_lru_bounds_entries_and_counts_evictions():
    cache = ResultCache(capacity=2)
    for s in (1, 2, 3):
        assert cache.put((s,), _result(s))
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.get((1,)) is None          # oldest evicted
    assert cache.get((3,)) is not None
    # a hit refreshes recency: key 3 survives the next insertion, key 2 dies
    cache.put((4,), _result(4))
    assert cache.get((3,)) is not None and cache.get((2,)) is None
    stats = cache.stats()
    assert stats["entries"] == 2 and stats["evictions"] == 2
    assert stats["hits"] == 2 and stats["misses"] == 2


def test_partials_rejected_and_hits_are_isolated_copies():
    cache = ResultCache(capacity=4)
    assert not cache.put(("p",), _result(9, missed=True))
    assert len(cache) == 0
    cache.put(("k",), _result(1))
    hit = cache.get(("k",), request=ClusterRequest(seed=1, deadline_ms=5.0))
    assert hit.request.deadline_ms == 5.0   # carries the incoming request
    hit.cluster[:] = -1                      # consumer mutates its copy...
    again = cache.get(("k",))
    assert np.array_equal(again.cluster, np.array([1, 2], np.int32))


def test_invalidate_clears():
    cache = ResultCache(capacity=4)
    cache.put(("k",), _result(1))
    cache.invalidate()
    assert len(cache) == 0 and cache.get(("k",)) is None


# ------------------------------------------------------------ engine wiring

def test_engine_cache_hits_bit_identical_and_lane_free(sbm_graph):
    eng = LocalClusterEngine(sbm_graph, batch_slots=4, **CAPS)
    reqs = [ClusterRequest(seed=s, alpha=0.05, eps=1e-4)
            for s in (3, 107, 211, 3)]      # seed 3 repeats
    # run() submits the whole list before draining, so the in-stream
    # duplicate enqueues before its twin completes — all 4 compute
    first = eng.run(reqs)
    injections = eng.stats["injections"]
    again = eng.run(reqs)
    # every repeat resolves from the cache: no lane was ever occupied
    assert eng.stats["injections"] == injections
    assert eng.stats["result_cache_hits"] >= len(reqs)
    for a, b in zip(first, again):
        assert a.conductance == b.conductance and a.size == b.size
        assert a.volume == b.volume and a.support == b.support
        assert a.pushes == b.pushes and a.iterations == b.iterations
        assert np.array_equal(a.cluster, b.cluster)
        assert not b.deadline_missed


def test_graph_version_bump_invalidates(sbm_graph):
    eng = LocalClusterEngine(sbm_graph, batch_slots=4, **CAPS)
    req = ClusterRequest(seed=3, alpha=0.05, eps=1e-4)
    eng.run([req])
    assert eng.cached_result(req) is not None
    eng.handle.bump_version()
    assert eng.cached_result(req) is None   # stale at the new version
    # recomputing at the new version repopulates it
    injections = eng.stats["injections"]
    eng.run([req])
    assert eng.stats["injections"] == injections + 1
    assert eng.cached_result(req) is not None


def test_cache_disabled_recomputes(sbm_graph):
    eng = LocalClusterEngine(sbm_graph, batch_slots=4, result_cache=0,
                             **CAPS)
    assert eng.result_cache is None
    req = ClusterRequest(seed=3, alpha=0.05, eps=1e-4)
    eng.run([req])
    injections = eng.stats["injections"]
    eng.run([req])
    assert eng.stats["injections"] == injections + 1   # really recomputed


def test_shared_cache_instance_across_engines(sbm_graph):
    shared = ResultCache(capacity=64)
    a = LocalClusterEngine(sbm_graph, batch_slots=4, result_cache=shared,
                           **CAPS)
    b = LocalClusterEngine(sbm_graph, batch_slots=4, result_cache=shared,
                           **CAPS)
    req = ClusterRequest(seed=3, alpha=0.05, eps=1e-4)
    ra = a.run([req])[0]
    # engine b never computed anything, yet serves a's converged answer
    rb = b.cached_result(req)
    assert rb is not None and rb.conductance == ra.conductance
    assert np.array_equal(rb.cluster, ra.cluster)

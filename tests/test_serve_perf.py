"""Steady-state re-trace freedom (serve/aot.py + the engine's hot path).

The serving overhaul's core claim: after ``LocalClusterEngine.warmup``, the
steady state never enters XLA again — bucket-ladder promotions hop between
already-compiled executables, and an LRU-evicted pool's re-creation is an
executable-cache hit, never a re-trace.  The guard counts actual backend
compiles through ``jax.monitoring`` (the same signal a profiler would see),
so a regression that sneaks a ``jit`` call into the tick path fails here
even if the engine's own ``aot_compiles`` accounting were wrong.
"""
import jax
import numpy as np

from repro.serve import ClusterRequest, LocalClusterEngine


def _unregister(listener) -> None:
    jax.monitoring.unregister_event_duration_listener(listener)


def test_steady_state_stream_never_recompiles(sbm_graph):
    # Small frontier/edge workspaces force mid-stream promotions; generous
    # sweep workspaces keep harvest on the AOT sweep (a sweep retry would
    # legitimately compile a doubled shape — that's the capacity ladder,
    # not the steady state).  lru_pools=1 forces pool eviction between the
    # two PR-Nibble statics families, so re-creation is exercised too.
    eng = LocalClusterEngine(
        sbm_graph, batch_slots=4, cap_f=1 << 8, cap_e=1 << 10,
        cap_n=1 << 10, sweep_cap_e=1 << 14, cap_v=1 << 8,
        max_cap_e=1 << 12, lru_pools=1, rounds_per_step=8)
    protos = [ClusterRequest(seed=0, optimized=True),
              ClusterRequest(seed=0, optimized=False),
              ClusterRequest(seed=0, backend="sparse")]
    w = eng.warmup(protos, max_bucket=eng.max_bucket)
    assert w["compiled"] == 3 * (eng.max_bucket + 1)
    # idempotent: a second warmup finds everything cached
    assert eng.warmup(protos, max_bucket=eng.max_bucket)["compiled"] == 0

    compiles = []

    def listener(event, duration, **kw):
        if "backend_compile" in event:
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        aot_before = eng.stats["aot_compiles"]
        rng = np.random.default_rng(3)
        cand = np.flatnonzero(np.asarray(sbm_graph.deg) > 0)
        reqs = []
        for i, s in enumerate(rng.choice(cand, size=12)):
            if i % 4 == 3:
                reqs.append(ClusterRequest(seed=int(s), alpha=0.01,
                                           eps=1e-4, backend="sparse"))
            else:
                # tight-eps requests overflow the small bucket-0 workspace
                # and promote up the warmed ladder
                reqs.append(ClusterRequest(seed=int(s), alpha=0.01,
                                           eps=(1e-6 if i % 3 == 0
                                                else 1e-4),
                                           optimized=bool(i % 2)))
        eng.run(reqs)
        assert eng.stats["promotions"] > 0      # the stream hopped buckets
        # drain's trailing eviction leaves one pool; run again so evicted
        # pools are re-created — from the executable cache, not XLA
        evicted = eng.stats["pools_evicted"]
        assert evicted > 0
        hits_before = eng.stats["aot_cache_hits"]
        # drop the seed→result cache so the rerun actually re-creates
        # pools (a result-cache hit would resolve lane-free and prove
        # nothing about executable reuse)
        eng.result_cache.invalidate()
        eng.run(reqs[:6])
        assert eng.stats["aot_cache_hits"] > hits_before
        assert eng.stats["aot_compiles"] == aot_before
        assert compiles == [], (
            f"steady state entered XLA {len(compiles)} times after warmup")
    finally:
        _unregister(listener)

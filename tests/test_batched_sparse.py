"""Batched sparse backend (core/batched_sparse.py) and the engine's
dense/sparse lane selection (serve/cluster_engine.py).

The contracts under test (docs/algorithms.md §Bit-identity guarantees):
per-seed outputs of ``batched_pr_nibble_sparse`` are *bit-identical* to
single-seed ``pr_nibble_sparse`` — including through the frontier/value
overflow ladder — the sparse sweep equals the rank-table sweep element for
element, and the engine routes requests to the lane type the heuristic (or
an explicit pin) demands while still matching the single-seed drivers.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (pr_nibble_sparse, sweep_cut,
                        sweep_cut_sparse, batched_pr_nibble,
                        batched_pr_nibble_sparse, batched_cluster_sparse,
                        batched_sparse_sweep_cut, sparse_rows_to_dense,
                        sparse_lane_footprint, pick_backend)
from repro.core.frontier import expand, pack_unique
from repro.core.pr_nibble_sparse import (pr_nibble_sparse_alive,
                                         pr_nibble_sparse_init,
                                         pr_nibble_sparse_round)
from repro.core.sparsevec import sv_lookup
from repro.graphs import rmat
from repro.serve import AsyncClusterEngine, ClusterRequest, LocalClusterEngine

# Right-sized workspaces for the small test graphs (see test_batched.py).
CAPS = dict(cap_f=1 << 10, cap_e=1 << 14, cap_v=1 << 12)
TINY = dict(cap_f=1 << 5, cap_e=1 << 7, cap_v=1 << 6)


def _mixed_params(graph, B, seed=0):
    rng = np.random.default_rng(seed)
    deg = np.asarray(graph.deg)
    seeds = rng.choice(np.flatnonzero(deg > 0), size=B).astype(np.int32)
    eps = rng.choice([1e-5, 1e-6], size=B).astype(np.float32)
    alpha = rng.choice([0.05, 0.01], size=B).astype(np.float32)
    return seeds, eps, alpha


def _assert_lane_matches(out, i, ref):
    """Lane i of a BatchedSparseDiffusionResult == a PRNibbleSparseResult."""
    k = int(out.p_count[i])
    assert k == int(ref.p.count)
    np.testing.assert_array_equal(out.p_ids[i, :k],
                                  np.asarray(ref.p.ids)[:k])
    np.testing.assert_array_equal(out.p_vals[i, :k],
                                  np.asarray(ref.p.vals)[:k])
    kr = int(out.r_count[i])
    assert kr == int(ref.r.count)
    np.testing.assert_array_equal(out.r_ids[i, :kr],
                                  np.asarray(ref.r.ids)[:kr])
    np.testing.assert_array_equal(out.r_vals[i, :kr],
                                  np.asarray(ref.r.vals)[:kr])
    assert int(out.pushes[i]) == int(ref.pushes)
    assert int(out.iterations[i]) == int(ref.iterations)


# ------------------------------------------------- (a) batched == single-seed

def test_batched_sparse_matches_single_seed(local_graph):
    """Mixed (α, ε) lanes, ample caps: every lane bit-identical to the
    single-seed sparse driver, one compiled bucket."""
    B = 16
    seeds, eps, alpha = _mixed_params(local_graph, B)
    out = batched_pr_nibble_sparse(local_graph, seeds, eps, alpha, **CAPS)
    for i in range(B):
        ref = pr_nibble_sparse(local_graph, int(seeds[i]), float(eps[i]),
                               float(alpha[i]), **CAPS)
        _assert_lane_matches(out, i, ref)
    assert not out.overflow.any()


def test_batched_sparse_matches_dense_backend(local_graph):
    """Cross-backend agreement: densified sparse p == dense p (float
    tolerance — reduction orders differ), same push counts."""
    B = 6
    seeds, eps, alpha = _mixed_params(local_graph, B, seed=1)
    sp = batched_pr_nibble_sparse(local_graph, seeds, eps, alpha, **CAPS)
    dn = batched_pr_nibble(local_graph, seeds, eps, alpha,
                           cap_f=1 << 10, cap_e=1 << 14)
    dense = sparse_rows_to_dense(sp.p_ids, sp.p_vals, sp.p_count,
                                 local_graph.n)
    np.testing.assert_allclose(dense, dn.p, atol=1e-6)
    np.testing.assert_array_equal(sp.pushes, dn.pushes)


# ------------------------------------------------- (b) frontier-overflow ladder

def test_sparse_overflow_ladder_promotion(local_graph):
    """Deliberately tiny (cap_f, cap_e, cap_v): every lane overflows the
    first buckets; the generalized ladder (frontier AND value capacity)
    climbs and results still equal the single-seed sparse driver, which
    retries on the same doubling schedule."""
    B = 8
    seeds, eps, alpha = _mixed_params(local_graph, B, seed=4)
    out = batched_pr_nibble_sparse(local_graph, seeds, eps, alpha, **TINY)
    assert not out.overflow.any()
    assert len(out.buckets) > 1          # promotions actually happened
    cap_es = [b[2] for b in out.buckets]
    assert cap_es == sorted(set(cap_es)), "each bucket dispatched once"
    cap_vs = [b[3] for b in out.buckets]
    assert all(v2 >= v1 for v1, v2 in zip(cap_vs, cap_vs[1:]))
    assert max(cap_vs) <= local_graph.n + 1     # cap_v clamps at n+1
    for i in range(B):
        ref = pr_nibble_sparse(local_graph, int(seeds[i]), float(eps[i]),
                               float(alpha[i]), **TINY)
        _assert_lane_matches(out, i, ref)


# ------------------------------------------------- (c) sparse sweep cut

def test_sweep_cut_sparse_matches_rank_table_sweep(local_graph):
    """sweep_cut_sparse (sorted-support lookup, O(cap_n+cap_e) memory)
    returns element-identical arrays to sweep_cut (dense rank table)."""
    for s in (5, 200, 1234):
        res = pr_nibble_sparse(local_graph, s, 1e-6, 0.05, **CAPS)
        a = sweep_cut(local_graph, res.p.ids, res.p.vals, res.p.count, 1 << 15)
        b = sweep_cut_sparse(local_graph, res.p.ids, res.p.vals, res.p.count,
                             1 << 15)
        np.testing.assert_array_equal(np.asarray(a.order), np.asarray(b.order))
        np.testing.assert_array_equal(np.asarray(a.cut), np.asarray(b.cut))
        np.testing.assert_array_equal(np.asarray(a.conductance),
                                      np.asarray(b.conductance))
        assert float(a.best_conductance) == float(b.best_conductance)
        assert int(a.best_size) == int(b.best_size)
        assert int(a.nnz) == int(b.nnz)


def test_batched_sparse_sweep_matches_per_lane(local_graph):
    B = 4
    seeds, eps, alpha = _mixed_params(local_graph, B, seed=3)
    out = batched_pr_nibble_sparse(local_graph, seeds, eps, alpha, **CAPS)
    sw = batched_sparse_sweep_cut(local_graph, jnp.asarray(out.p_ids),
                                  jnp.asarray(out.p_vals),
                                  jnp.asarray(out.p_count), 1 << 15)
    for i in range(B):
        ref = sweep_cut_sparse(local_graph, jnp.asarray(out.p_ids[i]),
                               jnp.asarray(out.p_vals[i]),
                               jnp.asarray(out.p_count[i]), 1 << 15)
        assert float(sw.best_conductance[i]) == float(ref.best_conductance)
        assert int(sw.best_size[i]) == int(ref.best_size)


def test_batched_cluster_sparse_fused(sbm_graph):
    """Fused sparse diffusion+sweep == sparse diffusion then sparse sweep."""
    B = 6
    rng = np.random.default_rng(5)
    seeds = rng.integers(0, sbm_graph.n, size=B).astype(np.int32)
    caps = dict(cap_f=1 << 10, cap_e=1 << 14, cap_v=1 << 10)
    out = batched_cluster_sparse(sbm_graph, seeds, 1e-6, 0.05,
                                 sweep_cap_e=1 << 14, **caps)
    assert not out.overflow.any()
    for i in range(B):
        ref = pr_nibble_sparse(sbm_graph, int(seeds[i]), 1e-6, 0.05, **caps)
        sw = sweep_cut_sparse(sbm_graph, ref.p.ids, ref.p.vals, ref.p.count,
                              1 << 14)
        assert float(out.best_conductance[i]) == float(sw.best_conductance)
        assert int(out.best_size[i]) == int(sw.best_size)
        assert int(out.pushes[i]) == int(ref.pushes)


# ------------------------------------------------- (d) engine backend selection

def test_engine_sparse_backend_matches_single_seed(local_graph):
    """backend="sparse" engine: mixed-parameter burst through sparse lanes,
    every result equal to single-seed sparse driver + sparse sweep."""
    B = 10
    seeds, eps, alpha = _mixed_params(local_graph, B, seed=6)
    reqs = [ClusterRequest(seed=int(s), alpha=float(a), eps=float(e),
                           backend="sparse")
            for s, e, a in zip(seeds, eps, alpha)]
    eng = LocalClusterEngine(local_graph, batch_slots=4, cap_f=1 << 10,
                             cap_e=1 << 14, cap_v=1 << 11, cap_n=1 << 10,
                             sweep_cap_e=1 << 15)
    results = eng.run(reqs)
    assert len(results) == B
    for r, q in zip(results, reqs):
        assert r.request is q
        assert r.backend == "sparse"
        ref = pr_nibble_sparse(local_graph, q.seed, q.eps, q.alpha,
                               cap_f=1 << 10, cap_e=1 << 14, cap_v=1 << 11)
        sw = sweep_cut_sparse(local_graph, ref.p.ids, ref.p.vals,
                              ref.p.count, 1 << 15)
        assert r.pushes == int(ref.pushes)
        assert r.conductance == float(sw.best_conductance)
        assert r.size == int(sw.best_size)
        assert not r.overflow
    assert eng.stats["completed"] == B


def test_engine_auto_backend_heuristic(local_graph):
    """auto mode picks by the graph-size/K rule; explicit pins override."""
    assert pick_backend(2000, 2048) == "dense"     # n < 2*4*2048
    assert pick_backend(2000, 128) == "sparse"     # n >= 2*4*128
    caps = dict(cap_f=1 << 10, cap_e=1 << 14, cap_n=1 << 10,
                sweep_cap_e=1 << 15)
    # big cap_v -> dense lanes
    eng = LocalClusterEngine(local_graph, batch_slots=2, cap_v=1 << 11, **caps)
    r = eng.run([ClusterRequest(seed=5, eps=1e-5)])[0]
    assert r.backend == "dense"
    # tiny cap_v -> sparse lanes; a dense pin on the same engine overrides
    eng = LocalClusterEngine(local_graph, batch_slots=2, cap_v=1 << 7, **caps)
    ra, rb = eng.run([ClusterRequest(seed=5, eps=1e-5),
                      ClusterRequest(seed=5, eps=1e-5, backend="dense")])
    assert ra.backend == "sparse"
    assert rb.backend == "dense"
    assert ra.pushes == rb.pushes      # same work either lane type
    assert ra.conductance == pytest.approx(rb.conductance, rel=1e-6)
    # hk_pr never rides sparse lanes: auto falls back, a pin is an error
    r = eng.run([ClusterRequest(seed=5, method="hk_pr", eps=1e-5)])[0]
    assert r.backend == "dense"
    # ... and an engine-wide sparse default also falls back (no error)
    eng_sp = LocalClusterEngine(local_graph, batch_slots=2, backend="sparse",
                                cap_v=1 << 7, **caps)
    r = eng_sp.run([ClusterRequest(seed=5, method="hk_pr", eps=1e-5)])[0]
    assert r.backend == "dense"
    with pytest.raises(ValueError, match="sparse"):
        eng.submit(ClusterRequest(seed=5, method="hk_pr", backend="sparse"))
    with pytest.raises(ValueError, match="unknown backend"):
        eng.submit(ClusterRequest(seed=5, backend="dens"))
    with pytest.raises(ValueError, match="unknown backend"):
        LocalClusterEngine(local_graph, backend="sprase")


def test_engine_sparse_overflow_promotion(local_graph):
    """Tiny sparse buckets: requests climb the ladder on sparse lanes and
    match the bucketed single-seed sparse driver."""
    seeds = [5, 105, 205]
    eng = LocalClusterEngine(local_graph, batch_slots=2, backend="sparse",
                             cap_f=1 << 5, cap_e=1 << 7, cap_v=1 << 6,
                             cap_n=1 << 8, sweep_cap_e=1 << 10)
    results = eng.run([ClusterRequest(seed=s, alpha=0.05, eps=1e-5)
                       for s in seeds])
    assert eng.stats["promotions"] > 0
    for r, s in zip(results, seeds):
        ref = pr_nibble_sparse(local_graph, s, 1e-5, 0.05,
                               cap_f=1 << 5, cap_e=1 << 7, cap_v=1 << 6)
        assert r.backend == "sparse"
        assert r.pushes == int(ref.pushes)
        assert not r.overflow
    shapes = eng.stats["bucket_shapes"]
    # (method, backend, ops_backend, B, f, e, topo) — topo None off-mesh
    assert all(len(sh) == 7 for sh in shapes)
    assert all(sh[-1] is None for sh in shapes)


# ------------------------------------------------- (e) memory accounting

def test_sparse_lane_footprint_accounting():
    fp = sparse_lane_footprint(cap_f=1 << 10, cap_e=1 << 14, cap_v=1 << 12)
    assert fp["state"] == 4 * (1 << 12)           # p,r × (ids, vals)
    assert fp["total"] == fp["state"] + fp["transient"]
    # the memory-bound claim: state is K-bounded, independent of any n
    assert fp["state"] < 2 * 50_000               # dense lane on randLocal-50k


# ------------------------------------------------- (f) next-frontier rule

# Small enough that the frontier, the edge workspace and the value sets each
# overflow in some case of the tight (α, ε) pair (R-MAT's hubs fill cap_e).
RULE_CAPS = dict(cap_f=1 << 8, cap_e=1 << 11, cap_v=1 << 10)


@pytest.fixture(scope="module")
def rmat_graph():
    return rmat(10, 8, seed=2)


def _candidate_frontier(graph, s, r_new, eps, cap_e):
    """The candidate pack the sparse round used to run: the pushed
    (``fids``) and pushed-to (``eb.dst``) vertices, each tested against
    the round's ``r_new``, sorted and deduped."""
    n = graph.n
    f = s.frontier
    fvalid = f.valid()
    fids = jnp.where(fvalid, f.ids, n)
    eb = expand(graph, f, cap_e)
    cands = jnp.concatenate([fids, eb.dst])
    cvalid = jnp.concatenate([fvalid, eb.valid])
    csafe = jnp.minimum(cands, n - 1)
    r_cand = sv_lookup(r_new, cands, n)
    keep = (cvalid & (graph.deg[csafe] > 0)
            & (r_cand >= graph.deg[csafe] * eps))
    return pack_unique(cands, keep, n, f.cap)


@pytest.mark.parametrize("eps,alpha,overflows",
                         [(1e-3, 0.1, False), (1e-4, 0.02, True)],
                         ids=["ample", "tight"])
@pytest.mark.parametrize("optimized", [True, False])
@pytest.mark.parametrize("graph_name", ["rmat_graph", "sbm_graph",
                                        "local_graph"])
def test_sparse_round_frontier_equals_candidate_pack(request, graph_name,
                                                     optimized, eps, alpha,
                                                     overflows):
    """After every round, the frontier taken from ``r``'s sorted support
    equals the candidate pack computed from the same pre-round state: ids,
    count and overflow flag, through the round where a capacity overflows."""
    graph = request.getfixturevalue(graph_name)
    cap_e = RULE_CAPS["cap_e"]

    @jax.jit
    def step(s, e, a):
        s2 = pr_nibble_sparse_round(graph, s, e, a, optimized, cap_e)
        return s2, _candidate_frontier(graph, s, s2.r, e, cap_e)

    deg = np.asarray(graph.deg)
    seeds = np.random.default_rng(0).choice(np.flatnonzero(deg > 0), size=6)
    rounds = 0
    overflowed = False
    for seed in seeds:
        s = pr_nibble_sparse_init(int(seed), graph.n, RULE_CAPS["cap_f"],
                                  RULE_CAPS["cap_v"])
        while bool(pr_nibble_sparse_alive(s)):
            s, want = step(s, jnp.float32(eps), jnp.float32(alpha))
            got = s.frontier
            np.testing.assert_array_equal(np.asarray(got.ids),
                                          np.asarray(want.ids))
            assert int(got.count) == int(want.count)
            assert bool(got.overflow) == bool(want.overflow)
            overflowed |= bool(s.overflow)
            rounds += 1
    assert rounds > len(seeds)
    assert overflowed == overflows


# ------------------------------------------------- (g) eps > 0 on sparse lanes

_SMALL = dict(cap_f=1 << 5, cap_e=1 << 7, cap_v=1 << 6)


def _submit_sparse(graph, eps, wrap):
    eng = LocalClusterEngine(graph, batch_slots=2, backend="sparse",
                             cap_n=1 << 8, sweep_cap_e=1 << 10, **_SMALL)
    # a dense lane takes any eps: the rule is the sparse round's
    eng._pool_key(ClusterRequest(seed=5, eps=eps, backend="dense"), 0)
    front = AsyncClusterEngine(eng) if wrap else eng
    front.submit(ClusterRequest(seed=5, eps=eps))


_ENTRY_POINTS = {
    "pr_nibble_sparse": lambda g, e: pr_nibble_sparse(g, 5, e, 0.05,
                                                      **_SMALL),
    "batched_pr_nibble_sparse": lambda g, e: batched_pr_nibble_sparse(
        g, [5, 6], [1e-4, e], 0.05, **_SMALL),
    "batched_cluster_sparse": lambda g, e: batched_cluster_sparse(
        g, [5, 6], [e, 1e-4], 0.05, **_SMALL),
    "engine": lambda g, e: _submit_sparse(g, e, wrap=False),
    "async_engine": lambda g, e: _submit_sparse(g, e, wrap=True),
}


@pytest.mark.parametrize("eps", [0.0, -1e-5, float("nan")])
@pytest.mark.parametrize("entry", sorted(_ENTRY_POINTS))
def test_sparse_pr_nibble_rejects_nonpositive_eps(local_graph, entry, eps):
    """ε ≤ 0 (or NaN) is refused before any lane runs: with it a zero
    residual would pass the support filter and the run would not end."""
    with pytest.raises(ValueError, match="eps > 0"):
        _ENTRY_POINTS[entry](local_graph, eps)

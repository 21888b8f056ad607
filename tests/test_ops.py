"""The op-dispatch layer (core/ops.py): backend parity + driver bit-identity.

Two contracts (docs/architecture.md §Op-dispatch layer):

  1. *Op parity* — for every op, the ``pallas`` backend (interpret mode on
     CPU) returns **bit-identical** results to the ``xla`` reference and to
     the structure-free oracles in ``kernels/ref.py``, across dtypes,
     duplicate-heavy index patterns, and empty/overflow inputs.  (The one
     exception is f32 ``prefix_sum``, whose blocked scan reassociates — the
     drivers only scan integers.)
  2. *Driver bit-identity* — every driver produces bit-identical outputs
     under ``backend="xla"`` and ``backend="pallas"``, single-seed and
     batched, dense and sparse.

Property tests need hypothesis (requirements-dev.txt); the fixed-case and
driver tests run regardless.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import ops
from repro.core import (pr_nibble, pr_nibble_sparse, hk_pr, evolving_sets,
                        sweep_cut, batched_pr_nibble,
                        batched_pr_nibble_sparse, batched_cluster,
                        batched_cluster_sparse)
from repro.kernels import ref
from repro.graphs import rand_local

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    HAVE_HYPOTHESIS = False

GRAPH = rand_local(400, degree=5, seed=3)
CAPS = dict(cap_f=1 << 8, cap_e=1 << 12)


def bitwise_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    return np.array_equal(np.atleast_1d(a).view(np.uint8),
                          np.atleast_1d(b).view(np.uint8))


# ---------------------------------------------------------------- registry

def test_registry_and_resolve():
    assert set(ops.backends()) >= {"xla", "pallas"}
    assert ops.resolve("auto") == "xla"     # on every platform
    assert ops.resolve("xla") == "xla"
    with pytest.raises(ValueError):
        ops.resolve("cuda")
    with pytest.raises(ValueError):
        ops.register_backend("bogus", not_an_op=lambda: None)


def test_register_backend_partial_falls_back_to_xla():
    ops.register_backend("_test_partial", prefix_sum=lambda x: jnp.cumsum(x))
    try:
        x = jnp.arange(5, dtype=jnp.int32)
        out = ops.prefix_sum(x, backend="_test_partial")
        assert bitwise_equal(out, jnp.cumsum(x))
        # unspecified op fell back to the xla reference
        vec = jnp.zeros(4, jnp.float32)
        got = ops.scatter_add(vec, jnp.array([1, 1]), jnp.array([1.0, 2.0]),
                              backend="_test_partial")
        assert bitwise_equal(got, np.array([0, 3, 0, 0], np.float32))
    finally:
        ops._REGISTRY.pop("_test_partial")


# ------------------------------------------------------------- scatter_add

@pytest.mark.parametrize("dtype", [np.float32, np.int32])
@pytest.mark.parametrize("case", ["dense_dups", "one_hot_collision", "empty",
                                  "all_invalid", "chunk_spill"])
def test_scatter_add_backend_parity(dtype, case):
    rng = np.random.default_rng(hash((str(dtype), case)) % 2**32)
    n = 300
    if case == "empty":
        m = 0
    elif case == "chunk_spill":
        m = 2000                      # >256 hits per tile → spill path
    else:
        m = 700
    if case == "one_hot_collision":
        idx = np.zeros(m, np.int32)   # every update lands on one slot
    else:
        idx = rng.integers(0, n, m).astype(np.int32)
    if dtype is np.float32:
        vals = (rng.random(m) - 0.3).astype(np.float32)
        vec = rng.random(n).astype(np.float32)
    else:
        vals = rng.integers(-5, 6, m).astype(np.int32)
        vec = rng.integers(0, 50, n).astype(np.int32)
    valid = np.ones(m, bool) if case != "all_invalid" else np.zeros(m, bool)
    if case == "dense_dups":
        valid = rng.random(m) < 0.8
    args = (jnp.asarray(vec), jnp.asarray(idx), jnp.asarray(vals),
            jnp.asarray(valid))
    want = ref.scatter_add_ref(*args)
    got_x = ops.scatter_add(*args, backend="xla")
    got_p = ops.scatter_add(*args, backend="pallas")
    assert bitwise_equal(got_x, want)
    assert bitwise_equal(got_p, want), f"pallas != ref for {dtype}/{case}"


def test_scatter_add_under_vmap_parity():
    rng = np.random.default_rng(0)
    B, n, m = 3, 200, 400
    vec = jnp.asarray(rng.random((B, n)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, n, (B, m)).astype(np.int32))
    vals = jnp.asarray(rng.random((B, m)).astype(np.float32))
    valid = jnp.asarray(rng.random((B, m)) < 0.7)
    import jax
    fx = jax.vmap(lambda v, i, w, ok: ops.scatter_add(v, i, w, ok,
                                                      backend="xla"))
    fp = jax.vmap(lambda v, i, w, ok: ops.scatter_add(v, i, w, ok,
                                                      backend="pallas"))
    assert bitwise_equal(fx(vec, idx, vals, valid), fp(vec, idx, vals, valid))


if HAVE_HYPOTHESIS:

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.integers(-5, 60), min_size=0, max_size=120),
           st.integers(0, 2**31 - 1))
    def test_scatter_add_property(idx, seed):
        """Random (possibly out-of-range, duplicate-heavy) index patterns:
        all three implementations agree bitwise."""
        rng = np.random.default_rng(seed)
        n = 50
        m = len(idx)
        idx = np.asarray(idx, np.int32)
        vals = (rng.random(m).astype(np.float32) * 2 - 0.5)
        vec = rng.random(n).astype(np.float32)
        valid = (idx >= 0) & (idx < n) & (rng.random(m) < 0.9)
        args = (jnp.asarray(vec), jnp.asarray(np.clip(idx, 0, n)),
                jnp.asarray(vals), jnp.asarray(valid))
        want = ref.scatter_add_ref(*args)
        assert bitwise_equal(ops.scatter_add(*args, backend="xla"), want)
        assert bitwise_equal(ops.scatter_add(*args, backend="pallas"), want)

    @settings(max_examples=30, deadline=None)
    @given(st.lists(st.integers(0, 40), min_size=0, max_size=150),
           st.integers(2, 64), st.integers(0, 2**31 - 1))
    def test_segment_merge_property(ids, cap, seed):
        """Duplicate-heavy merges at arbitrary capacity (incl. overflowing):
        xla, pallas, and the dense oracle agree bitwise on every leaf."""
        rng = np.random.default_rng(seed)
        n = 40
        ids = np.asarray(ids + [n] * 7, np.int32)   # sentinel tail
        vals = rng.random(ids.shape[0]).astype(np.float32)
        args = (jnp.asarray(ids), jnp.asarray(vals))
        want = ref.segment_merge_ref(*args, n, cap)
        got_x = ops.segment_merge(*args, n, cap, backend="xla")
        got_p = ops.segment_merge(*args, n, cap, backend="pallas")
        for w, gx, gp in zip(want, got_x, got_p):
            assert bitwise_equal(gx, w)
            assert bitwise_equal(gp, w)

    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 3000), st.integers(0, 2**31 - 1),
           st.sampled_from(["int32", "float32"]))
    def test_prefix_sum_property(size, seed, dtype):
        rng = np.random.default_rng(seed)
        if dtype == "int32":
            x = rng.integers(-100, 100, size).astype(np.int32)
        else:
            x = rng.random(size).astype(np.float32)
        got_x = ops.prefix_sum(jnp.asarray(x), backend="xla")
        got_p = ops.prefix_sum(jnp.asarray(x), backend="pallas")
        assert bitwise_equal(got_x, jnp.cumsum(jnp.asarray(x)))
        if dtype == "int32":
            assert bitwise_equal(got_p, got_x)   # int scans are exact
        else:
            np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x),
                                       rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------- segment_merge

def test_segment_merge_empty_and_overflow():
    n, cap = 30, 4
    ids = jnp.full((10,), n, jnp.int32)               # all sentinel
    vals = jnp.ones((10,), jnp.float32)
    for backend in ("xla", "pallas"):
        out_ids, out_vals, count = ops.segment_merge(ids, vals, n, cap,
                                                     backend=backend)
        assert int(count) == 0
        assert np.all(np.asarray(out_ids) == n)
        assert np.all(np.asarray(out_vals) == 0)
    # 8 distinct ids into cap=4: count reports the uncapped support
    ids = jnp.asarray(np.arange(8, dtype=np.int32))
    vals = jnp.asarray(np.ones(8, np.float32))
    a = ops.segment_merge(ids, vals, n, cap, backend="xla")
    b = ops.segment_merge(ids, vals, n, cap, backend="pallas")
    assert int(a[2]) == int(b[2]) == 8
    for x, y in zip(a, b):
        assert bitwise_equal(x, y)


def test_segment_merge_spans_kernel_blocks():
    """Runs crossing the kernel's BLK boundaries still fold in stream order
    (the carried-scalar stitch)."""
    from repro.kernels.segment_merge import BLK
    rng = np.random.default_rng(5)
    n = 10
    tot = 3 * BLK + 17                    # few ids → giant runs across blocks
    ids = np.sort(rng.integers(0, n, tot)).astype(np.int32)
    perm = rng.permutation(tot)           # op sorts internally
    vals = rng.random(tot).astype(np.float32)
    args = (jnp.asarray(ids[perm]), jnp.asarray(vals))
    a = ops.segment_merge(*args, n, 16, backend="xla")
    b = ops.segment_merge(*args, n, 16, backend="pallas")
    for x, y in zip(a, b):
        assert bitwise_equal(x, y)


# -------------------------------------------------- driver bit-identity

def _assert_result_bitwise(a, b):
    for name, x in a._asdict().items():
        y = getattr(b, name)
        if isinstance(x, tuple):      # nested NamedTuple (SparseVec) / buckets
            if hasattr(x, "_asdict"):
                _assert_result_bitwise(x, y)
            else:
                assert x == y
        else:
            assert bitwise_equal(x, y), f"field {name} differs"


def test_pr_nibble_backend_bit_identity():
    a = pr_nibble(GRAPH, 11, eps=1e-5, alpha=0.05, **CAPS)
    b = pr_nibble(GRAPH, 11, eps=1e-5, alpha=0.05, backend="pallas", **CAPS)
    _assert_result_bitwise(a, b)


def test_pr_nibble_beta_backend_bit_identity():
    a = pr_nibble(GRAPH, 11, eps=1e-5, alpha=0.05, beta=0.5, **CAPS)
    b = pr_nibble(GRAPH, 11, eps=1e-5, alpha=0.05, beta=0.5,
                  backend="pallas", **CAPS)
    _assert_result_bitwise(a, b)


def test_pr_nibble_sparse_backend_bit_identity():
    a = pr_nibble_sparse(GRAPH, 11, eps=1e-5, alpha=0.05, cap_v=1 << 9, **CAPS)
    b = pr_nibble_sparse(GRAPH, 11, eps=1e-5, alpha=0.05, cap_v=1 << 9,
                         backend="pallas", **CAPS)
    _assert_result_bitwise(a, b)


def test_hk_pr_backend_bit_identity():
    a = hk_pr(GRAPH, 11, N=8, eps=1e-4, t=5.0, **CAPS)
    b = hk_pr(GRAPH, 11, N=8, eps=1e-4, t=5.0, backend="pallas", **CAPS)
    _assert_result_bitwise(a, b)


def test_evolving_sets_backend_bit_identity():
    import jax
    key = jax.random.PRNGKey(4)
    a = evolving_sets(GRAPH, 11, T=12, B=20000, phi=0.3, cap_s=1 << 8,
                      cap_e=1 << 12, key=key)
    b = evolving_sets(GRAPH, 11, T=12, B=20000, phi=0.3, cap_s=1 << 8,
                      cap_e=1 << 12, key=key, backend="pallas")
    _assert_result_bitwise(a, b)


def test_sweep_cut_backend_bit_identity():
    res = pr_nibble(GRAPH, 11, eps=1e-5, alpha=0.05, **CAPS)
    p = np.asarray(res.p)
    nz = np.flatnonzero(p > 0).astype(np.int32)
    cap_n = 1 << 9
    assert nz.size <= cap_n
    ids = np.full(cap_n, GRAPH.n, np.int32)
    ids[: nz.size] = nz
    vals = np.zeros(cap_n, np.float32)
    vals[: nz.size] = p[nz]
    a = sweep_cut(GRAPH, jnp.asarray(ids), jnp.asarray(vals),
                  jnp.asarray(nz.size), 1 << 12)
    b = sweep_cut(GRAPH, jnp.asarray(ids), jnp.asarray(vals),
                  jnp.asarray(nz.size), 1 << 12, backend="pallas")
    _assert_result_bitwise(a, b)


def test_batched_drivers_backend_bit_identity():
    seeds = np.array([3, 7, 11, 19], np.int32)
    a = batched_pr_nibble(GRAPH, seeds, 1e-5, 0.05, **CAPS)
    b = batched_pr_nibble(GRAPH, seeds, 1e-5, 0.05, backend="pallas", **CAPS)
    for name in ("p", "r", "iterations", "pushes", "overflow"):
        assert bitwise_equal(getattr(a, name), getattr(b, name)), name

    sa = batched_pr_nibble_sparse(GRAPH, seeds, 1e-5, 0.05, cap_v=1 << 9,
                                  **CAPS)
    sb = batched_pr_nibble_sparse(GRAPH, seeds, 1e-5, 0.05, cap_v=1 << 9,
                                  backend="pallas", **CAPS)
    for name in ("p_ids", "p_vals", "p_count", "r_ids", "r_vals", "r_count",
                 "iterations", "pushes", "overflow"):
        assert bitwise_equal(getattr(sa, name), getattr(sb, name)), name

    ca = batched_cluster(GRAPH, seeds, 1e-5, 0.05, cap_n=1 << 8,
                         sweep_cap_e=1 << 12, **CAPS)
    cb = batched_cluster(GRAPH, seeds, 1e-5, 0.05, cap_n=1 << 8,
                         sweep_cap_e=1 << 12, backend="pallas", **CAPS)
    for name in ("conductance", "best_conductance", "best_size",
                 "best_volume", "support", "pushes", "iterations",
                 "overflow"):
        assert bitwise_equal(getattr(ca, name), getattr(cb, name)), name

    fa = batched_cluster_sparse(GRAPH, seeds, 1e-5, 0.05, cap_v=1 << 9,
                                sweep_cap_e=1 << 12, **CAPS)
    fb = batched_cluster_sparse(GRAPH, seeds, 1e-5, 0.05, cap_v=1 << 9,
                                sweep_cap_e=1 << 12, backend="pallas", **CAPS)
    for name in ("conductance", "best_conductance", "best_size",
                 "best_volume", "support", "pushes", "iterations",
                 "overflow"):
        assert bitwise_equal(getattr(fa, name), getattr(fb, name)), name


def test_engine_ops_backend_identity_and_pinning():
    from repro.serve import ClusterRequest, LocalClusterEngine
    eng_caps = dict(cap_f=1 << 8, cap_e=1 << 12, cap_n=1 << 8,
                    sweep_cap_e=1 << 12)
    reqs = [ClusterRequest(seed=s, eps=1e-5, alpha=0.05)
            for s in (3, 7, 11, 19)]
    ra = LocalClusterEngine(GRAPH, batch_slots=4, ops_backend="xla",
                            **eng_caps).run(reqs)
    rb = LocalClusterEngine(GRAPH, batch_slots=4, ops_backend="pallas",
                            **eng_caps).run(reqs)
    for a, b in zip(ra, rb):
        assert a.conductance == b.conductance
        assert a.size == b.size
        assert np.array_equal(a.cluster, b.cluster)
        assert (a.ops_backend, b.ops_backend) == ("xla", "pallas")
    # per-request pins coexist in one engine (separate pools, same results)
    eng = LocalClusterEngine(GRAPH, batch_slots=4, **eng_caps)
    mixed = eng.run([ClusterRequest(seed=3, eps=1e-5, alpha=0.05,
                                    ops_backend="pallas"),
                     ClusterRequest(seed=3, eps=1e-5, alpha=0.05,
                                    ops_backend="xla")])
    assert mixed[0].conductance == mixed[1].conductance
    assert {m.ops_backend for m in mixed} == {"pallas", "xla"}

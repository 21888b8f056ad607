"""Property tests for the frontier machinery + sort-merge sparse sets
(hypothesis) — the paper's §3 primitives."""
import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="property suite needs hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core.frontier import (Frontier, compact_sorted, expand,
                                 pack_unique, singleton)
from repro.core.sparsevec import (sv_empty, sv_from_pairs, sv_lookup,
                                  sv_merge_add, sv_update_existing)
from repro.graphs import rand_local

GRAPH = rand_local(300, degree=4, seed=7)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 299), min_size=1, max_size=40, unique=True))
def test_expand_enumerates_exactly_adjacency(ids):
    g = GRAPH.to_numpy()
    cap_f, cap_e = 64, 4096
    f_ids = np.full(cap_f, GRAPH.n, np.int32)
    f_ids[: len(ids)] = sorted(ids)
    f = Frontier(ids=jnp.asarray(f_ids), count=jnp.asarray(len(ids), jnp.int32),
                 overflow=jnp.asarray(False))
    eb = expand(GRAPH, f, cap_e)
    got = sorted(zip(np.asarray(eb.src)[np.asarray(eb.valid)],
                     np.asarray(eb.dst)[np.asarray(eb.valid)]))
    want = sorted((v, int(w)) for v in sorted(ids)
                  for w in g.indices[g.indptr[v]: g.indptr[v + 1]])
    assert got == want
    assert int(eb.total) == len(want)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 99), min_size=1, max_size=200),
       st.integers(0, 2**31 - 1))
def test_pack_unique_is_sorted_set(cands, seed):
    rng = np.random.default_rng(seed)
    keep = rng.random(len(cands)) < 0.7
    arr = jnp.asarray(np.asarray(cands, np.int32))
    f = pack_unique(arr, jnp.asarray(keep), n=100, cap_out=128)
    got = np.asarray(f.ids)[: int(f.count)].tolist()
    want = sorted({c for c, k in zip(cands, keep) if k})
    assert got == want
    assert not bool(f.overflow)


def test_pack_unique_overflow_flag():
    cands = jnp.arange(100, dtype=jnp.int32)
    f = pack_unique(cands, jnp.ones(100, bool), n=1000, cap_out=16)
    assert bool(f.overflow)
    assert int(f.count) == 16


@pytest.mark.parametrize("room", [-5, 0, 7], ids=["below", "at", "above"])
def test_compact_sorted_equals_pack_unique(room):
    """On sorted, duplicate-free, sentinel-padded ids (a SparseVec's
    support) the sort-free compaction returns what ``pack_unique`` returns
    — ids, count, overflow and the truncation — for ``cap_out`` below, at
    and above the kept count, and the same bits on both op backends."""
    n, cap = 1000, 96
    rng = np.random.default_rng(11)
    live = np.sort(rng.choice(n, size=80, replace=False)).astype(np.int32)
    ids = np.full(cap, n, np.int32)
    ids[: live.size] = live
    keep = rng.random(cap) < 0.6
    keep[live.size + 3] = True          # a kept sentinel stays out
    k = int(keep[: live.size].sum())
    cap_out = k + room
    ids, keep = jnp.asarray(ids), jnp.asarray(keep)
    want = pack_unique(ids, keep, n, cap_out)
    for backend in ("xla", "pallas"):
        got = compact_sorted(ids, keep, n, cap_out, backend=backend)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(want.count) == min(k, cap_out)
    assert bool(want.overflow) == (room < 0)


@settings(max_examples=25, deadline=None)
@given(st.dictionaries(st.integers(0, 63), st.floats(0.01, 10.0),
                       max_size=20),
       st.lists(st.tuples(st.integers(0, 63), st.floats(0.01, 5.0)),
                max_size=30))
def test_sparsevec_merge_add_matches_dict(base, updates):
    n, cap = 64, 128
    ids = np.fromiter(base.keys(), np.int32, len(base))
    vals = np.fromiter(base.values(), np.float32, len(base))
    pad = cap - len(ids)
    sv = sv_from_pairs(jnp.asarray(np.pad(ids, (0, pad))),
                       jnp.asarray(np.pad(vals, (0, pad))),
                       jnp.arange(cap) < len(ids), cap, n)
    uid = np.asarray([u[0] for u in updates] + [0], np.int32)
    uval = np.asarray([u[1] for u in updates] + [0.0], np.float32)
    uvalid = jnp.arange(uid.shape[0]) < len(updates)
    out = sv_merge_add(sv, jnp.asarray(uid), jnp.asarray(uval), uvalid, n)

    want = dict(base)
    for k, v in updates:
        want[k] = want.get(k, 0.0) + v
    got = {int(i): float(v) for i, v in
           zip(np.asarray(out.ids)[: int(out.count)],
               np.asarray(out.vals)[: int(out.count)])}
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4)
    # ids stay sorted
    sorted_ids = np.asarray(out.ids)[: int(out.count)]
    assert np.all(np.diff(sorted_ids) > 0)


def test_sparsevec_lookup_missing_is_zero():
    sv = sv_empty(16, 100)
    sv = sv_merge_add(sv, jnp.asarray([3, 7], jnp.int32),
                      jnp.asarray([1.5, 2.5], jnp.float32),
                      jnp.asarray([True, True]), 100)
    q = sv_lookup(sv, jnp.asarray([3, 4, 7, 99], jnp.int32), 100)
    np.testing.assert_allclose(np.asarray(q), [1.5, 0.0, 2.5, 0.0])


def test_sparsevec_update_existing():
    sv = sv_from_pairs(jnp.asarray([1, 5, 9, 0], jnp.int32),
                       jnp.asarray([1., 2., 3., 0.], jnp.float32),
                       jnp.asarray([True, True, True, False]), 8, 100)
    sv = sv_update_existing(sv, jnp.asarray([5, 9], jnp.int32),
                            jnp.asarray([0.0, 7.0], jnp.float32),
                            jnp.asarray([True, True]))
    q = sv_lookup(sv, jnp.asarray([1, 5, 9], jnp.int32), 100)
    np.testing.assert_allclose(np.asarray(q), [1.0, 0.0, 7.0])

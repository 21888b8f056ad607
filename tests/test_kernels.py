"""Per-kernel parity sweeps vs the plain oracles in kernels/ref.py
(interpret mode off-TPU).  The scatter fold and the run fold are exact by
construction, so they are checked bit for bit; int32 scans are exact too."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref, scatter_accum, segment_merge


def bitwise_equal(a, b):
    a, b = np.atleast_1d(np.asarray(a)), np.atleast_1d(np.asarray(b))
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


# ------------------------------------------------------------- scatter_fold

@pytest.mark.parametrize("n,m,dtype,pattern", [
    (300, 700, np.float32, "uniform"),
    (300, 700, np.int32, "uniform"),
    (1, 50, np.float32, "uniform"),                 # one destination
    (scatter_accum.GROUP + 5, 4000, np.float32, "uniform"),   # two groups
    (3 * scatter_accum.GROUP, 2000, np.float32, "out_of_range"),
    # one group's range of the stream spans two SMEM blocks
    (300, scatter_accum.GROUP + 700, np.float32, "spill"),
    (300, scatter_accum.GROUP + 700, np.int32, "spill"),
    # two groups, each spanning blocks, sharing the block between them
    (scatter_accum.GROUP + 5, 3 * scatter_accum.BLOCK + 11, np.float32,
     "uniform"),
    (128, 0, np.float32, "uniform"),                # no contributions
])
def test_scatter_fold_matches_left_fold(n, m, dtype, pattern):
    rng = np.random.default_rng(n + m)
    if pattern == "out_of_range":
        idx = rng.integers(-20, n + 20, m)
    elif pattern == "spill":
        idx = rng.integers(0, 8, m)                 # few hot destinations
    else:
        idx = rng.integers(0, n, m)
    idx = idx.astype(np.int32)
    if dtype is np.float32:
        vec = rng.random(n).astype(np.float32)
        vals = (rng.random(m) - 0.4).astype(np.float32)
    else:
        vec = rng.integers(-50, 50, n).astype(np.int32)
        vals = rng.integers(-9, 10, m).astype(np.int32)
    want = ref.scatter_add_ref(vec, idx, vals, np.ones(m, bool))
    got = ops.scatter_fold(jnp.asarray(vec), jnp.asarray(idx),
                           jnp.asarray(vals))
    assert bitwise_equal(got, want)


def test_scatter_fold_under_vmap():
    rng = np.random.default_rng(1)
    B, n, m = 3, 500, 900
    vec = rng.random((B, n)).astype(np.float32)
    idx = rng.integers(0, n, (B, m)).astype(np.int32)
    vals = rng.random((B, m)).astype(np.float32)
    got = jax.vmap(ops.scatter_fold)(jnp.asarray(vec), jnp.asarray(idx),
                                     jnp.asarray(vals))
    for b in range(B):
        want = ref.scatter_add_ref(vec[b], idx[b], vals[b], np.ones(m, bool))
        assert bitwise_equal(got[b], want)


def test_scatter_fold_under_nested_vmap_with_a_shared_vector():
    rng = np.random.default_rng(2)
    A, B, n, m = 2, 2, scatter_accum.GROUP + 9, scatter_accum.BLOCK + 300
    vec = rng.random(n).astype(np.float32)
    idx = rng.integers(0, n, (A, B, m)).astype(np.int32)
    vals = rng.random((A, B, m)).astype(np.float32)
    fold = jax.vmap(jax.vmap(ops.scatter_fold, in_axes=(None, 0, 0)),
                    in_axes=(None, 0, 0))
    got = jax.jit(fold)(jnp.asarray(vec), jnp.asarray(idx), jnp.asarray(vals))
    for a in range(A):
        for b in range(B):
            want = ref.scatter_add_ref(vec, idx[a, b], vals[a, b],
                                       np.ones(m, bool))
            assert bitwise_equal(got[a, b], want)


def test_scatter_fold_rejects_64bit_and_16bit():
    with pytest.raises(TypeError):
        ops.scatter_fold(jnp.zeros(4, jnp.bfloat16), jnp.zeros(2, jnp.int32),
                         jnp.ones(2, jnp.bfloat16))


# -------------------------------------------------------------- prefix scan

@pytest.mark.parametrize("n", [0, 1, 127, 1024, 3000, 7 * 1024])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_prefix_sum(n, dtype):
    rng = np.random.default_rng(n)
    if dtype is np.int32:
        x = rng.integers(-100, 100, n).astype(np.int32)
    else:
        x = rng.random(n).astype(np.float32)
    y = ops.prefix_sum(jnp.asarray(x))
    if dtype is np.int32:
        assert bitwise_equal(y, np.cumsum(x, dtype=np.int32))
    else:
        np.testing.assert_allclose(np.asarray(y), np.cumsum(x), rtol=1e-5)


def test_prefix_sum_under_vmap():
    rng = np.random.default_rng(2)
    x = rng.integers(-5, 6, (4, 2500)).astype(np.int32)
    y = jax.vmap(ops.prefix_sum)(jnp.asarray(x))
    assert bitwise_equal(y, np.cumsum(x, axis=1, dtype=np.int32))


# --------------------------------------------------------- segment fold/merge

@pytest.mark.parametrize("blocks,ids", [(1, 5), (3, 7), (2, 1000)])
def test_fold_runs_is_the_left_fold(blocks, ids):
    """Runs spanning SMEM blocks keep one left fold (the carried scalar)."""
    rng = np.random.default_rng(blocks * ids)
    tot = blocks * segment_merge.SUB * segment_merge.BLK
    s = np.sort(rng.integers(0, ids, tot))
    first = np.concatenate([[1], s[1:] != s[:-1]]).astype(np.int32)
    vals = (rng.random(tot) - 0.3).astype(np.float32)
    got = segment_merge.fold_runs(
        jnp.asarray(first.reshape(-1, segment_merge.BLK)),
        jnp.asarray(vals.reshape(-1, segment_merge.BLK)),
        interpret=ops.interpret())
    assert bitwise_equal(np.asarray(got).reshape(-1),
                         ref.fold_runs_ref(first, vals))


@pytest.mark.parametrize("tot,n,cap", [(0, 10, 4), (40, 10, 16), (9000, 50, 64),
                                       (300, 400, 8)])
def test_segment_merge_sorted_matches_oracle(tot, n, cap):
    rng = np.random.default_rng(tot + n)
    ids = np.sort(rng.integers(0, n + 1, tot)).astype(np.int32)  # n: sentinel
    vals = rng.random(tot).astype(np.float32)
    want = ref.segment_merge_ref(jnp.asarray(ids), jnp.asarray(vals), n, cap)
    got = ops.segment_merge_sorted(jnp.asarray(ids), jnp.asarray(vals), n, cap)
    for w, g in zip(want, got):
        assert bitwise_equal(w, g)


def test_interpret_decided_by_platform(monkeypatch):
    assert ops.interpret() == (jax.default_backend() != "tpu")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.interpret() is False

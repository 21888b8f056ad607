"""The device CSR builds equal ``repro.graphs.build_csr`` bit for bit."""
import numpy as np
import pytest

import jax

from repro.graphs import build_csr as host_build_csr

from bench.graphs import csr, rmat

CFG = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)


def _same(dev, host):
    assert (dev.n, dev.m) == (host.n, host.m)
    for name in ("indptr", "indices", "deg"):
        a, b = np.asarray(getattr(dev, name)), np.asarray(getattr(host, name))
        assert a.dtype == b.dtype == np.int32, name
        assert np.array_equal(a, b), name


@pytest.mark.parametrize("seed", [0, 2**31 + 3])
@pytest.mark.parametrize("scale", [8, 10])
def test_device_csr_equals_host_build(scale, seed):
    src, dst, n = rmat.generate(jax.random.key(seed), dict(CFG, scale=scale))
    m = int(csr.count_unique(src, dst, n))
    edges = np.stack([np.asarray(src), np.asarray(dst)], axis=1)
    _same(csr.build_csr(src, dst, n, m), host_build_csr(edges, n))


def test_fixed_edge_count_keeps_the_first_unique_pairs():
    src, dst, n = rmat.generate(jax.random.key(5), CFG)
    m = int(csr.count_unique(src, dst, n)) - 7
    s, d = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    lo, hi = np.minimum(s, d), np.maximum(s, d)
    key = np.unique((lo * n + hi)[lo != hi])[:m]
    kept = np.stack([key // n, key % n], axis=1)
    _same(csr.build_csr(src, dst, n, m), host_build_csr(kept, n))


def test_too_few_edges_is_an_error():
    src, dst, n = rmat.generate(jax.random.key(1), dict(CFG, scale=8))
    m = int(csr.count_unique(src, dst, n))
    with pytest.raises(ValueError, match="fewer than"):
        csr.build_csr(src, dst, n, m + 1)

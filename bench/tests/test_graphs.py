"""The device graph builds equal ``repro.graphs``' layouts bit for bit: the
one-device CSR equals ``repro.graphs.build_csr``, and the sharded build over
several devices equals ``partition_rows`` of the one-device CSR."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax

from repro.graphs import build_csr as host_build_csr
from repro.graphs.handle import as_local_csr

from bench import harness
from bench.graphs import csr, rmat
from bench.reference import HostGraph

CFG = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)
ROOT = harness.ROOT


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


def test_one_device_build_is_the_edges_csr():
    key = harness.graph_key(2**33 + 9)
    src, dst, n = rmat.generate(key, CFG)
    m = int(csr.count_unique(src, dst, n)) - 3
    handle = rmat.build(key, dict(CFG, undirected_edges=m), jax.devices()[:1])
    assert not handle.is_sharded
    _same(as_local_csr(handle), csr.build_csr(src, dst, n, m))


@pytest.mark.parametrize("size", [16384, 4096, 1024])
def test_blocks_draw_the_edges_stream(size):
    key = harness.graph_key(2**32 + 17)
    src, dst = rmat.edges(key, **rmat._params(CFG))
    args = (key, rmat._perm(key, CFG["scale"]))
    draw = jax.jit(lambda k: rmat.block(args, k, size=size, scale=10,
                                        a=0.57, b=0.19, c=0.19))
    drawn = [draw(k) for k in range(16384 // size)]
    for i, whole in enumerate((src, dst)):
        blocks = np.concatenate([np.asarray(d[i]) for d in drawn])
        assert np.array_equal(blocks, np.asarray(whole))


def test_host_copy_of_a_handle_is_the_csr():
    key = harness.graph_key(11)
    src, dst, n = rmat.generate(key, CFG)
    m = int(csr.count_unique(src, dst, n))
    host = HostGraph.of(rmat.build(key, dict(CFG, undirected_edges=m),
                                   jax.devices()[:1]))
    g = csr.build_csr(src, dst, n, m)
    assert np.array_equal(host.indptr, np.asarray(g.indptr, np.int64))
    assert np.array_equal(host.indices, np.asarray(g.indices))
    assert np.array_equal(host.deg, np.asarray(g.deg))


# ------------------------------------------------- several devices (dist)

_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np
import jax
from repro.graphs import partition_rows
from bench import harness
from bench.graphs import csr, rmat, shards
from bench.reference import HostGraph

CFG = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)
rmat.BLOCK = 2048                    # a stream of 8 blocks
devices = jax.devices()
out = {}


def same(handle, graph, num):
    pg, ref = handle.partitioned(), partition_rows(graph, num)
    return bool(handle.is_sharded and handle.mesh is not None and
                (pg.n, pg.m, pg.rows_per, pg.n_true, pg.num_shards) ==
                (ref.n, ref.m, ref.rows_per, ref.n_true, ref.num_shards) and
                all(np.asarray(getattr(pg, f)).dtype == np.int32 and
                    np.array_equal(np.asarray(getattr(pg, f)),
                                   np.asarray(getattr(ref, f)))
                    for f in ("indptr", "indices", "deg")))


def held_by_lo(src, dst, n, below):
    # unique undirected pairs whose lo is under ``below``
    s, d = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    lo, hi = np.minimum(s, d), np.maximum(s, d)
    key = np.unique((lo * n + hi)[lo != hi])
    return int(np.count_nonzero(key // n < below))


# R-MAT over 4 devices, the cutoff inside a shard and on shard boundaries
key = harness.graph_key(2**31 + 5)
src, dst, n = rmat.generate(key, CFG)
found = int(csr.count_unique(src, dst, n))
cuts = {"all": found, "last_shard": found - 7, "middle_shard": found // 2,
        "shard_end": held_by_lo(src, dst, n, 2 * n // 4),
        "shard_start": held_by_lo(src, dst, n, n // 4) + 1}
def budget(slots):
    # buffer slots a counting and a building pass may take, whatever the
    # devices' memory: None makes one pass of each
    shards.plan = lambda *_: slots


for name, m in cuts.items():
    for passes, slots in (("one", (None, None)), ("many", (1500, 2500))):
        budget(slots)
        h = rmat.build(key, dict(CFG, undirected_edges=m), devices)
        out[f"rmat4_{name}_{passes}"] = same(h, csr.build_csr(
            src, dst, n, m), 4)

# n not divisible by D: the stream's ids taken mod 1001, 3 sentinel rows
n7 = 1001
perm = rmat._perm(key, CFG["scale"])
def draw(args, k):
    return tuple(x % n7 for x in rmat.block(args, k, size=2048, scale=10,
                                            a=0.57, b=0.19, c=0.19))


s7, d7 = (np.concatenate(x) for x in zip(*[
    [np.asarray(y) for y in draw((key, perm), k)] for k in range(8)]))
m7 = int(csr.count_unique(s7, d7, n7)) - 11
budget((1500, 2500))
h = shards.build(draw, (key, perm), 8, n7, m7, devices)
out["uneven_rows"] = same(h, csr.build_csr(s7, d7, n7, m7), 4) and \
    h.partitioned().num_padded == 3

# one stream, one graph, on any number of devices
m = found - 5
graph = csr.build_csr(src, dst, n, m)
one = HostGraph.of(rmat.build(key, dict(CFG, undirected_edges=m),
                              devices[:1]))
hosts = {}
for num in (2, 4):
    h = rmat.build(key, dict(CFG, undirected_edges=m), devices[:num])
    out[f"same_graph_d{num}"] = same(h, graph, num)
    hosts[num] = HostGraph.of(h)
out["host_copy_of_shards"] = all(
    np.array_equal(getattr(hosts[num], f), getattr(one, f))
    and getattr(hosts[num], f).dtype == getattr(one, f).dtype
    for num in hosts for f in ("indptr", "indices", "deg")) and \
    hosts[4].indptr.dtype == np.int64
out["degrees_of_shards"] = bool(np.array_equal(h.degrees(),
                                               np.asarray(graph.deg)))

try:
    rmat.build(key, dict(CFG, undirected_edges=found + 1), devices)
    out["too_few_edges"] = False
except ValueError as e:
    out["too_few_edges"] = "fewer than" in str(e)
print("RESULT:" + json.dumps(out))
"""

CASES = ([f"rmat4_{c}_{p}" for c in ("all", "last_shard", "middle_shard",
                                     "shard_end", "shard_start")
          for p in ("one", "many")]
         + ["uneven_rows", "same_graph_d2", "same_graph_d4",
            "host_copy_of_shards", "degrees_of_shards", "too_few_edges"])


@pytest.fixture(scope="module")
def sharded():
    """The sharded builds' checks, on 4 virtual CPU devices in a child
    process (the device count is fixed before JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(_SHARDED)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT:")]
    return json.loads(line[0][len("RESULT:"):])


@pytest.mark.dist
@pytest.mark.parametrize("case", CASES)
def test_sharded_build_equals_partitioned_csr(sharded, case):
    assert sharded[case] is True

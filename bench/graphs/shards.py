"""Build a graph's rows over several devices, and read any graph back.

:func:`build` gives ``partition_rows(csr.build_csr(src, dst, n, m), D)`` of
``repro.graphs`` bit for bit, for the edge list that ``draw`` gives block by
block, without any device holding the whole edge list, the whole CSR or
another shard's rows:

* layout: device ``d`` owns rows ``[d·R, (d+1)·R)``, ``R = ceil(n/D)``; rows
  from ``n`` on are isolated sentinels, ``indptr`` is local to the shard
  (int32), column ids are global and ``indices`` is padded with ``R·D``;
* passes: a pass of a device draws the whole stream, block by block, and
  keeps in a buffer of fixed capacity the edges whose row lies in a
  sub-range of the device's rows; the device's memory sets how many
  sub-ranges there are;
* the first ``m`` unique ``(lo, hi)`` pairs: a counting round keeps each
  edge once, as ``lo→hi``, and gives each sub-range the number of unique
  pairs whose ``lo`` it holds.  Their prefix over the sub-ranges in row
  order places the ``m``-th pair, which one more counting pass reads.  The
  round's first pass also counts the copies each of ``BINS`` slices of a
  shard's rows will hold;
* the building round keeps both copies, ``lo→hi`` and ``hi→lo``, of every
  pair up to the cutoff, in sub-ranges made of whole slices; it sorts them
  as ``csr._rows`` orders a row, drops duplicates and appends the rest to
  the shard.

Counts that can pass 2³¹ (directed entries, 2m) are Python ints on the
host.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.graphs import GraphHandle, PartitionedCSR
from repro.graphs.handle import as_local_csr

AXIS = "data"
BINS = 64               # slices of a shard's rows the counting round sizes
SLACK = 1.25            # counting buffer slots over the edges a pass expects
COUNT_BYTES = 24        # device bytes a counting buffer slot costs, sort in
BUILD_BYTES = 32        # the same for a building slot
DRAW_BYTES = 48         # device bytes an edge of a block costs while drawn
BUDGET = 0.7            # share of the smallest device memory a build plans
EMPTY = np.iinfo(np.int32).max


def _collect(draw, args, blocks: int, cap: int, pick, tally=None,
             zero=0):
    """``pick(lo, hi) -> (take, src, dst)`` over every block: the taken
    ``(src, dst)`` in a buffer of ``cap`` slots (``src`` EMPTY in free
    ones), how many were taken (saturating at int32), and the sum of
    ``tally(lo, hi)`` over the blocks."""
    def body(k, carry):
        bs, bd, count, tallied = carry
        s, t = draw(args, k)
        lo, hi = jnp.minimum(s, t), jnp.maximum(s, t)
        take, src, dst = pick(lo, hi)
        pos = jnp.minimum(count, cap) + jnp.cumsum(take, dtype=jnp.int32) - 1
        pos = jnp.where(take, pos, cap)
        bs = bs.at[pos].set(src, mode="drop")
        bd = bd.at[pos].set(dst, mode="drop")
        took = jnp.sum(take, dtype=jnp.int32)
        if tally is not None:
            tallied = tallied + tally(lo, hi)
        return bs, bd, jnp.minimum(count, EMPTY - took) + took, tallied

    return lax.fori_loop(0, blocks, body, (
        jnp.full((cap,), EMPTY, jnp.int32), jnp.zeros((cap,), jnp.int32),
        jnp.int32(0), jnp.asarray(zero, jnp.int32)))


def _first(a, b):
    """First of each run of equal ``(a, b)`` in a sorted buffer."""
    return jnp.concatenate([jnp.ones((1,), bool),
                            (a[1:] != a[:-1]) | (b[1:] != b[:-1])])


def _count(args, bounds, rank, *, draw, blocks: int, rows: int, cap: int):
    """Counting pass of one device over the ``lo`` rows ``[r0, r1)``:
    ``[edges taken, unique pairs, the pair of rank rank, then the copies
    of each slice of the shard's rows where the pass is told to count]``."""
    r0, r1, row0, slices = (bounds[0, i] for i in range(4))
    ends = row0 + jnp.minimum(
        jnp.arange(1, BINS + 1, dtype=jnp.int32) * -(-rows // BINS), rows)

    def pick(lo, hi):
        return (lo != hi) & (lo >= r0) & (lo < r1), lo, hi

    def tally(lo, hi):
        def below(end):
            return (jnp.sum((lo != hi) & (lo >= row0) & (lo < end),
                            dtype=jnp.int32)
                    + jnp.sum((lo != hi) & (hi >= row0) & (hi < end),
                              dtype=jnp.int32))
        return lax.cond(slices > 0, lambda: lax.map(below, ends),
                        lambda: jnp.zeros((BINS,), jnp.int32))

    bs, bd, taken, upto = _collect(draw, args, blocks, cap, pick, tally,
                                   jnp.zeros((BINS,), jnp.int32))
    lo, hi = lax.sort((bs, bd), num_keys=2)
    held = jnp.cumsum(_first(lo, hi) & (lo < EMPTY), dtype=jnp.int32)
    at = jnp.argmax(held > rank[0, 0])
    return jnp.concatenate([jnp.stack([taken, held[-1], lo[at], hi[at]]),
                            jnp.diff(upto, prepend=0)])[None]


def _build(args, bounds, shard, *, draw, blocks: int, n: int, pad: int,
           cap: int):
    """Building pass of one device over its rows ``[r0, r1)``: the copies
    of the pairs up to the cutoff, in row order, appended to the shard's
    ``(indices, deg, fill)``."""
    r0, r1, row0, cut_lo, cut_hi = (bounds[0, i] for i in range(5))
    indices, deg, fill = (x[0] for x in shard)

    def pick(lo, hi):
        kept = (lo != hi) & ((lo < cut_lo) | ((lo == cut_lo)
                                              & (hi <= cut_hi)))
        src = jnp.concatenate([lo, hi])
        return (jnp.concatenate([kept, kept]) & (src >= r0) & (src < r1),
                src, jnp.concatenate([hi, lo]))

    bs, bd, _, _ = _collect(draw, args, blocks, cap, pick)
    key = bd - bs - 1
    key = jnp.where(key < 0, key + n, key)
    src, key = lax.sort((bs, jnp.where(bs < n, key, 0)), num_keys=2)
    dst = src + key + 1
    dst = jnp.where(dst >= n, dst - n, dst)
    new = _first(src, key) & (src < n)
    pos = jnp.where(new, jnp.cumsum(new, dtype=jnp.int32) - 1, cap)
    vals = jnp.full((cap,), pad, jnp.int32).at[pos].set(dst, mode="drop")
    rows = deg.shape[0]
    deg = deg.at[jnp.where(new, src - row0, rows)].add(1, mode="drop")
    indices = lax.dynamic_update_slice(indices, vals, (fill,))
    fill = fill + jnp.sum(new, dtype=jnp.int32)
    return indices[None], deg[None], fill[None]


def _round(slots: float) -> int:
    return -(-math.ceil(slots) // 1024) * 1024


def plan(edges: int, n: int, devices) -> "tuple[int | None, int | None]":
    """Buffer slots a counting and a building pass may take on the
    smallest device's memory, for ``edges`` drawn edges over ``devices``
    (None where the backend does not say)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    if not all(limits):
        return None, None
    num = len(devices)
    free = (BUDGET * min(limits) - 4 * n - 8 * -(-n // num)
            - DRAW_BYTES * min(edges, 1 << 24))
    most = (int(free // COUNT_BYTES),
            int((free - 8 * edges // num) // BUILD_BYTES))
    if min(most) <= 0:
        raise MemoryError(f"{edges} edges do not fit {num} devices")
    return most


def _runs(copies: np.ndarray, most: "int | None") -> list:
    """Per device, the slice each building pass starts at: runs of whole
    slices of at most ``most`` copies."""
    starts = []
    for loads in copies:
        if most is not None and loads.max() > most:
            raise MemoryError(f"a slice of {int(loads.max())} copies passes "
                              f"the {most} a pass holds")
        begin, total = [0], 0
        for i, load in enumerate(loads):
            if most is not None and total + load > most:
                begin.append(i)
                total = 0
            total += int(load)
        starts.append(begin)
    return starts


def build(draw, args, blocks: int, n: int, m: int, devices) -> GraphHandle:
    """The CSR of the first ``m`` unique undirected edges of the stream
    ``draw(args, k)``, ``k < blocks``, in range shards over ``devices``.

    Raises ``ValueError`` when the stream has fewer than ``m`` unique
    undirected edges."""
    num = len(devices)
    mesh = Mesh(np.array(devices), (AXIS,))
    rows = -(-n // num)
    size = jax.eval_shape(draw, args, 0)[0].shape[0]
    args = jax.device_put(args, NamedSharding(mesh, P()))
    most_count, most_build = plan(blocks * size, n, devices)
    first = np.arange(num, dtype=np.int64) * rows

    def program(fn, shard_spec, donate=()):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(), P(AXIS), shard_spec),
            out_specs=shard_spec, check_vma=False), donate_argnums=donate)

    def on_rows(array):
        return jax.device_put(np.asarray(array, np.int32),
                              NamedSharding(mesh, P(AXIS)))

    # counting round: each edge once, as lo→hi, in equal sub-ranges
    expect = blocks * size / num
    passes = 1 if most_count is None else math.ceil(SLACK * expect
                                                    / most_count)
    cap = _round(SLACK * expect / passes)
    while True:
        count = program(functools.partial(
            _count, draw=draw, blocks=blocks, rows=rows, cap=cap), P(AXIS))

        def counted(p, rank, slices=0):
            bounds = np.stack([first + p * rows // passes,
                               first + (p + 1) * rows // passes, first,
                               np.full(num, slices)], axis=1)
            return np.asarray(count(args, on_rows(bounds), on_rows(rank)))

        none = np.zeros((num, 1))
        counts = np.stack([counted(p, none, slices=int(p == 0))
                           for p in range(passes)], axis=1).astype(np.int64)
        worst = int(counts[..., 0].max())       # [device, pass, _count's]
        if worst <= cap:
            break
        if most_count is None or worst <= most_count:
            cap = _round(worst)                 # a sub-range with a hub
        else:
            passes *= 2
            cap = _round(SLACK * expect / passes)
    held = counts[..., 1].reshape(-1)           # (device, pass) in row order
    found = int(held.sum())
    if found < m:
        raise ValueError(f"edge list has {found} unique undirected edges, "
                         f"fewer than the {m} the configuration keeps")
    at = int(np.searchsorted(np.cumsum(held), m))
    d, p = divmod(at, passes)
    rank = np.full((num, 1), -1)
    rank[d] = m - int(held[:at].sum()) - 1
    cut = counted(p, rank)[d, 2:4]

    # building round: both copies, in runs of whole slices
    copies = counts[:, 0, 4:]
    starts = _runs(copies, most_build)
    cap = _round(max(int(np.add.reduceat(c, s).max())
                     for c, s in zip(copies, starts)))
    width = int(copies.sum(axis=1).max()) + cap
    if width >= 2**31:
        raise ValueError(f"a shard would hold over 2^31 entries; build "
                         f"over more than {num} devices")
    step = program(functools.partial(_build, draw=draw, blocks=blocks, n=n,
                                     pad=rows * num, cap=cap),
                   (P(AXIS),) * 3, donate=(2,))
    shard_arrays = jax.jit(lambda: (
        jnp.full((num, width), rows * num, jnp.int32),
        jnp.zeros((num, rows), jnp.int32), jnp.zeros((num,), jnp.int32)),
        out_shardings=NamedSharding(mesh, P(AXIS)))()  # made on the devices
    slice_rows = -(-rows // BINS)
    for p in range(max(map(len, starts))):
        ends = np.array([(s + [BINS])[p: p + 2] if p < len(s) else
                         [BINS, BINS] for s in starts]) * slice_rows
        bounds = np.concatenate([first[:, None] + np.minimum(ends, rows),
                                 first[:, None],
                                 np.broadcast_to(cut, (num, 2))], axis=1)
        shard_arrays = step(args, on_rows(bounds), shard_arrays)
    del args
    return _finish(*shard_arrays, n=n, m=m, mesh=mesh)


def _finish(indices, deg, fill, *, n: int, m: int, mesh) -> GraphHandle:
    """The handle over the built shards: ``indices`` cut to the fullest
    shard, ``indptr`` from the degrees."""
    num, rows = deg.shape
    nnz = np.asarray(fill).astype(np.int64)
    if int(nnz.sum()) != 2 * m:
        raise AssertionError(f"shards hold {int(nnz.sum())} directed "
                             f"entries, not 2m = {2 * m}")
    width = max(1, int(nnz.max()))

    def cut(indices, deg):
        indptr = jnp.concatenate([jnp.zeros((1, 1), jnp.int32),
                                  jnp.cumsum(deg, axis=1, dtype=jnp.int32)],
                                 axis=1)
        return indices[:, :width], indptr

    indices, indptr = jax.jit(jax.shard_map(
        cut, mesh=mesh, in_specs=(P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS)), check_vma=False))(indices, deg)
    jax.block_until_ready(indices)
    pg = PartitionedCSR(indptr=indptr, indices=indices, deg=deg,
                        n=num * rows, m=m, num_shards=num, rows_per=rows,
                        n_true=n)
    return GraphHandle.from_partitioned(pg, mesh, axis=AXIS)


# --------------------------------------------------------- reading back

def _host_rows(array):
    """``array``'s rows on the host, one device's shard at a time."""
    for part in sorted(array.addressable_shards,
                       key=lambda s: s.index[0].start or 0):
        yield from np.asarray(part.data)


def host_csr(handle) -> "tuple[np.ndarray, np.ndarray]":
    """``(indptr int64[n+1], indices int32[2m])`` on the host, read from
    the local CSR or from the shards one at a time, padding dropped."""
    n, nnz = handle.n, 2 * handle.m
    if not handle.is_sharded:
        g = as_local_csr(handle)
        return np.asarray(g.indptr).astype(np.int64), np.asarray(g.indices)
    pg = handle.partitioned()
    indptr = np.zeros(n + 1, np.int64)
    indices = np.empty(nnz, np.int32)
    start = at = 0
    for local, slab in zip(_host_rows(pg.indptr), _host_rows(pg.indices)):
        stop = min(start + pg.rows_per, n)
        if stop > start:
            local = local[: stop - start + 1].astype(np.int64)
            indptr[start + 1: stop + 1] = at + local[1:]
            indices[at: at + local[-1]] = slab[: local[-1]]
            at += int(local[-1])
        start = stop
    if at != nnz:
        raise ValueError(f"shards hold {at} directed entries, not {nnz}")
    return indptr, indices

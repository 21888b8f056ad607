"""Distributed local graph clustering — the paper's engine at pod scale.

The paper targets one shared-memory node.  At 10⁹+-vertex scale the state
vectors and the graph no longer fit one chip, so this module lifts the
frontier-synchronous push to a *vertex-partitioned* SPMD program under
``shard_map``:

  * vertices are range-partitioned: device d owns rows
    [d·rows_per, (d+1)·rows_per)  (graphs/partition.py);
  * ``p``/``r`` live sharded (each device holds its slice);
  * each round, every device expands its *local* frontier from its CSR slab,
    producing (global dst, value) contributions;
  * contributions are routed to their owners with a **bucketed all_to_all**:
    sort by owner, slice per-owner buckets of static capacity, exchange,
    local scatter-add — message volume ∝ boundary mass, the distributed
    analogue of the paper's work-locality;
  * termination is a replicated carried scalar (psum of frontier sizes), so
    every device runs the same number of rounds — frontier-synchronous, like
    the paper's rounds, with the ICI all_to_all replacing the shared memory.

The same machinery drives distributed PR-Nibble here and is reused by the
multi-pod dry-run configs (launch/dryrun.py `graph_*` cells).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.graphs.partition import PartitionedCSR
from . import ops
from .frontier import scatter_add_dense, scatter_set_dense

__all__ = ["DistPRNibbleResult", "dist_pr_nibble",
           "build_dist_pr_nibble", "local_frontier_pack",
           "push_shares", "owner_buckets"]


class DistPRNibbleResult(NamedTuple):
    p: jnp.ndarray           # f32[n_true]  (padded sentinel rows sliced off)
    r: jnp.ndarray           # f32[n_true]
    iterations: jnp.ndarray  # int32 (replicated)
    pushes: jnp.ndarray      # int32 global pushes
    overflow: jnp.ndarray    # bool
    exchanged: jnp.ndarray = None  # int32 — cross-shard contribution slots
    #   routed over all rounds (the exchange volume the boundary-mass
    #   locality argument bounds; see benchmarks/dist_batched_bench.py).
    #   None only if constructed by legacy callers that predate the field.


class _Shard(NamedTuple):
    p: jnp.ndarray           # f32[rows_per] local slice
    r: jnp.ndarray
    t: jnp.ndarray           # replicated scalars
    pushes: jnp.ndarray
    global_front: jnp.ndarray
    overflow: jnp.ndarray
    exchanged: jnp.ndarray   # replicated int32 — cross-shard routed slots


@jax.named_scope("expand")
def _local_expand(indptr, indices, deg, f_loc, f_valid, cap_e, rows_per,
                  backend="xla"):
    """Expand a local frontier (local ids) against the local CSR slab.
    Returns (slot, dst_global, evalid, total)."""
    degs = jnp.where(f_valid, deg[jnp.minimum(f_loc, rows_per - 1)], 0)
    offs = ops.prefix_sum(degs, backend=backend) - degs
    total = offs[-1] + degs[-1]
    j = jnp.arange(cap_e, dtype=jnp.int32)
    slot = jnp.searchsorted(offs, j, side="right").astype(jnp.int32) - 1
    slot = jnp.clip(slot, 0, f_loc.shape[0] - 1)
    within = j - offs[slot]
    evalid = j < total
    row = jnp.minimum(f_loc[slot], rows_per - 1)
    base = indptr[row]
    eidx = jnp.clip(base + within, 0, indices.shape[0] - 1)
    dst = jnp.where(evalid & f_valid[slot], indices[eidx], jnp.int32(2**30))
    return slot, dst, evalid & f_valid[slot], total


# Shared round primitives — these encode the *fold-order-critical* pieces of
# the bit-identity guarantee (docs/algorithms.md #7), so they exist exactly
# once and both distributed engines (this single-seed one and the batched
# core/batched_dist.py) call them.

_GLOBAL_SENTINEL = 2 ** 30   # "nowhere" destination for masked slots


@jax.named_scope("frontier")
def local_frontier_pack(r_loc, deg, eps, rows_per: int, cap_f: int,
                        backend: str = "xla"):
    """Pack local ids with ``r >= d*eps`` (deg > 0) ascending into ``cap_f``
    slots.  Ascending local order is load-bearing: concatenated device-major
    it reproduces the single-chip sorted frontier.  Returns (ids, cnt) with
    ``cnt`` the *unclamped* above-threshold count (callers clamp/flag)."""
    above = (r_loc >= deg * eps) & (deg > 0)
    cnt = jnp.sum(above).astype(jnp.int32)
    pos = ops.prefix_sum(above.astype(jnp.int32), backend=backend) - 1
    ids = scatter_set_dense(
        jnp.full((cap_f,), rows_per, jnp.int32), pos,
        jnp.arange(rows_per, dtype=jnp.int32), above)
    return ids, cnt


def push_shares(rf, dv, alpha, optimized: bool):
    """The Fig 3 / Fig 4 push-rule arithmetic: (p_gain, r_self, share) for
    frontier residuals ``rf`` over degrees ``dv`` — identical expressions to
    :func:`repro.core.pr_nibble.pr_nibble_round`, which the bit-identity of
    every distributed driver depends on."""
    if optimized:                      # Fig 4 (optimal step size)
        return ((2.0 * alpha / (1.0 + alpha)) * rf,
                jnp.zeros_like(rf),
                ((1.0 - alpha) / (1.0 + alpha)) * rf / dv)
    return (alpha * rf,                # Fig 3
            (1.0 - alpha) * rf / 2.0,
            (1.0 - alpha) * rf / (2.0 * dv))


def owner_buckets(dst, contrib, evalid, D: int, rows_per: int, cap_x: int,
                  cap_e: int):
    """Route (dst, contrib) slots into per-owner buckets [D, cap_x] for the
    all_to_all.  The argsort is *stable*, preserving each owner's slots in
    expansion-stream order — with the source-major concatenation on the
    receive side this reproduces the single-chip scatter fold order.
    Returns (owner, send_dst, send_val, x_ovf)."""
    owner = jnp.where(evalid, dst // rows_per, D)   # D = invalid
    order = jnp.argsort(owner)                      # stable
    owner_s = owner[order]
    dst_s = dst[order]
    val_s = contrib[order]
    rng_d = jnp.arange(D, dtype=jnp.int32)
    start = jnp.searchsorted(owner_s, rng_d, side="left")
    count = (jnp.searchsorted(owner_s, rng_d, side="right")
             - start).astype(jnp.int32)
    x_ovf = jnp.any(count > cap_x)
    gidx = start[:, None] + jnp.arange(cap_x, dtype=jnp.int32)[None, :]
    in_bucket = jnp.arange(cap_x, dtype=jnp.int32)[None, :] < count[:, None]
    gsafe = jnp.clip(gidx, 0, cap_e - 1)
    send_dst = jnp.where(in_bucket, dst_s[gsafe], jnp.int32(_GLOBAL_SENTINEL))
    send_val = jnp.where(in_bucket, val_s[gsafe], 0.0)
    return owner, send_dst, send_val, x_ovf



def build_dist_pr_nibble(mesh, axis: str = "data", exchange: str = "a2a",
                         backend: str = "xla"):
    """Build the shard_map'd distributed PR-Nibble for a given mesh axis.

    ``exchange`` selects the contribution-routing collective:
      * "a2a"  — bucketed all_to_all (message volume ∝ boundary mass; the
                 locality-preserving scheme, default);
      * "psum" — naive baseline: scatter into a full dense [n] buffer and
                 all-reduce it (O(n) bytes per round regardless of frontier
                 size — what the roofline comparison in §Perf quantifies).

    ``backend`` routes every per-device scatter-add/scan through
    :mod:`repro.core.ops` (the same op layer the single-chip drivers use —
    the distributed engine is local pushes + a collective, nothing more).

    Returns fn(pg_arrays..., x, eps, alpha) -> DistPRNibbleResult, jit-able
    with in_shardings placing the partition slabs and state on `axis`.
    """
    D = mesh.shape[axis]

    def engine(indptr, indices, deg, x, eps, alpha, *, rows_per: int,
               cap_f: int, cap_e: int, cap_x: int, max_iters: int):
        """Runs INSIDE shard_map: args are per-device slabs.
        indptr: int32[1, rows_per+1]; indices: int32[1, nnz]; deg: int32[1, rows_per]
        x: int32 replicated seed; returns sharded p, r + replicated stats."""
        indptr = indptr[0]
        indices = indices[0]
        deg = deg[0]
        me = jax.lax.axis_index(axis)
        base = me * rows_per

        def cond(s: _Shard):
            return (s.global_front > 0) & (~s.overflow) & (s.t < max_iters)

        def body(s: _Shard) -> _Shard:
            f_loc, cnt = local_frontier_pack(s.r, deg, eps, rows_per, cap_f,
                                             backend)
            f_cnt = jnp.minimum(cnt, cap_f)
            f_ovf = cnt > cap_f
            f_valid = jnp.arange(cap_f, dtype=jnp.int32) < f_cnt
            safe = jnp.minimum(f_loc, rows_per - 1)
            rf = jnp.where(f_valid, s.r[safe], 0.0)
            dv = jnp.maximum(deg[safe], 1)

            p_gain, r_self, share = push_shares(rf, dv, alpha, True)

            p_new = scatter_add_dense(s.p, f_loc, p_gain, f_valid,
                                      backend=backend)
            r_new = scatter_set_dense(s.r, f_loc, r_self, f_valid)

            slot, dst, evalid, etot = _local_expand(
                indptr, indices, deg, f_loc, f_valid, cap_e, rows_per,
                backend)
            e_ovf = etot > cap_e   # silently-truncated expansion must retry
            contrib = jnp.where(evalid, share[slot], 0.0)

            if exchange == "psum":
                # naive baseline: dense global buffer + all-reduce
                dense = scatter_add_dense(
                    jnp.zeros((rows_per * D,), jnp.float32), dst, contrib,
                    evalid, backend=backend)
                dense = jax.lax.psum(dense, axis)
                mine_slice = jax.lax.dynamic_slice_in_dim(
                    dense, base, rows_per, 0)
                r_new = r_new + mine_slice
                x_ovf = jnp.asarray(False)
                exch = jnp.asarray(0, jnp.int32)
            else:
                # ---- bucketed all_to_all routing ----
                owner, send_dst, send_val, x_ovf = owner_buckets(
                    dst, contrib, evalid, D, rows_per, cap_x, cap_e)
                recv_dst = jax.lax.all_to_all(send_dst, axis, 0, 0, tiled=True)
                recv_val = jax.lax.all_to_all(send_val, axis, 0, 0, tiled=True)
                # local scatter-add: global → local ids
                loc = recv_dst.reshape(-1) - base
                ok = (loc >= 0) & (loc < rows_per)
                r_new = scatter_add_dense(r_new, loc, recv_val.reshape(-1),
                                          ok, backend=backend)
                exch = jnp.sum((owner != me) & evalid).astype(jnp.int32)

            # replicated termination stats
            nxt_above = jnp.sum((r_new >= deg * eps) & (deg > 0))
            gfront = jax.lax.psum(nxt_above, axis)
            gpush = jax.lax.psum(f_cnt, axis)
            gexch = jax.lax.psum(exch, axis)
            ovf = jax.lax.psum((f_ovf | x_ovf | e_ovf).astype(jnp.int32),
                               axis) > 0
            return _Shard(p=p_new, r=r_new, t=s.t + 1,
                          pushes=s.pushes + gpush,
                          global_front=gfront.astype(jnp.int32),
                          overflow=s.overflow | ovf,
                          exchanged=s.exchanged + gexch)

        # init: seed owner puts mass 1 (drop-sentinel masked — the non-owner
        # previously relied on adding 0.0 at a clipped in-range index)
        r0 = jnp.zeros((rows_per,), jnp.float32)
        mine = (x >= base) & (x < base + rows_per)
        r0 = scatter_add_dense(r0, jnp.clip(x - base, 0, rows_per - 1),
                               jnp.float32(1.0), mine)
        s0 = _Shard(p=jnp.zeros((rows_per,), jnp.float32), r=r0,
                    t=jnp.asarray(0, jnp.int32),
                    pushes=jnp.asarray(0, jnp.int32),
                    global_front=jnp.asarray(1, jnp.int32),
                    overflow=jnp.asarray(False),
                    exchanged=jnp.asarray(0, jnp.int32))
        s = jax.lax.while_loop(cond, body, s0)
        return s.p, s.r, s.t, s.pushes, s.overflow, s.exchanged

    def make(rows_per: int, cap_f: int, cap_e: int, cap_x: int,
             max_iters: int = 10_000):
        eng = functools.partial(engine, rows_per=rows_per, cap_f=cap_f,
                                cap_e=cap_e, cap_x=cap_x, max_iters=max_iters)
        smapped = shard_map(
            eng, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(), P(), P()),
            out_specs=(P(axis), P(axis), P(), P(), P(), P()))
        return smapped

    return make


def dist_pr_nibble(graph, mesh=None, x: int = 0, eps: float = 1e-7,
                   alpha: float = 0.01, axis: str = "data",
                   cap_f: int = 1 << 12, cap_e: int = 1 << 16,
                   cap_x: int = 1 << 12, max_cap_e: int = 1 << 24,
                   backend: str = "xla") -> DistPRNibbleResult:
    """Driver: distributed PR-Nibble (optimized rule) with bucket retry.

    ``graph`` is any graph-like (`repro.graphs.handle.as_handle`):
    a ``PartitionedCSR`` (then ``mesh`` is required), a ``CSRGraph`` to
    shard over ``mesh``, or a sharded ``GraphHandle`` carrying its own mesh.
    The returned ``p``/``r`` are sliced to the true vertex count — the
    partition's sentinel padding never escapes this driver.
    """
    from repro.graphs.handle import as_handle
    handle = as_handle(graph, mesh=mesh, axis=axis)
    mesh = handle.require_mesh()
    axis = handle.axis
    pg = handle.partitioned()
    make = build_dist_pr_nibble(mesh, axis, backend=backend)
    n_true = pg.n_true
    while True:
        fn = jax.jit(make(pg.rows_per, cap_f, cap_e, cap_x))
        p, r, t, pushes, ovf, exch = fn(
            pg.indptr, pg.indices, pg.deg,
            jnp.asarray(x, jnp.int32), jnp.float32(eps), jnp.float32(alpha))
        if not bool(ovf) or cap_e >= max_cap_e:
            return DistPRNibbleResult(p=p.reshape(-1)[:n_true],
                                      r=r.reshape(-1)[:n_true],
                                      iterations=t, pushes=pushes,
                                      overflow=ovf, exchanged=exch)
        cap_f = min(cap_f * 2, pg.rows_per + 1)
        cap_e *= 2
        cap_x = min(cap_x * 2, cap_e)

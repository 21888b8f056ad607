"""Batched sparse multi-seed local clustering — memory-bounded many-seed serving.

The dense batched engine (core/batched.py) answers B queries in one dispatch
but materializes B × f32[n] state vectors: on a billion-edge graph a 64-seed
batch is 256 GB of ``p``/``r`` before the first push.  That loses exactly the
locality the paper parallelizes — local algorithms do work (and, in
Spielman–Teng's original formulation, hold memory) proportional to the
*cluster*, not the graph.  This module restores that profile under vmap:
every lane carries only a compacted sparse ``(ids, vals)`` pair of capacity
``cap_v`` (the lane's K), a frontier of capacity ``cap_f``, and an edge
workspace of capacity ``cap_e`` — per-lane live values are O(K), independent
of n.

Layers:

  * :func:`batched_pr_nibble_sparse_fixedcap` — vmap of the single-seed
    sparse kernel: seeds[B] with per-seed (ε, α), shared static
    ``(cap_f, cap_e, cap_v)``.  XLA's while-loop batching masks finished
    lanes, so each lane's trajectory is identical to the single-seed run.
  * :func:`batched_sparse_sweep_cut` — vmap of
    :func:`repro.core.sweep.sweep_cut_sparse`: the sweep gathers only
    touched vertices (sorted-support rank lookup), so B sweeps cost
    B·O(cap_v + cap_e), never B·O(n).
  * :func:`batched_cluster_sparse_fixedcap` — the fused diffusion + sparse
    sweep kernel (the sparse analogue of ``batched_cluster_fixedcap``),
    which never materializes any dense vector at all.
  * Host drivers :func:`batched_pr_nibble_sparse` /
    :func:`batched_cluster_sparse` — per-seed overflow retry on the
    capacity ladder of core/batched.py, generalized over the *frontier/value*
    capacities: a lane that overflows any of (cap_f, cap_e, cap_v) is
    repacked into a power-of-two retry batch one bucket up
    (``cap_f``/``cap_v`` clamped at n+1, ``cap_e`` unclamped until
    ``max_cap_e``) — verbatim the schedule of
    :func:`repro.core.pr_nibble_sparse.pr_nibble_sparse`, so per-seed
    results are bit-identical to the single-seed sparse driver.

Overflow/retry contract and recompile boundaries are those documented in
core/batched.py; the only new static axis is ``cap_v``.  Because the retry
loop is the shared :func:`repro.core.batched._bucketed_retry`, sparse
ladder dispatches annotate an active trace scope
(:func:`repro.serve.tracing.annotate` — bucket hops, overflow counts,
pushes) exactly like the dense driver's, with no serve import here.  The dense-vs-sparse
serving decision (:func:`pick_backend`) and the per-lane memory accounting
(:func:`sparse_lane_footprint`) live here so the engine and the benchmarks
agree on one definition.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from . import ops as _ops
from .batched import (_bucketed_retry, _prep_batch, _CapLadder,
                      LaneKernels as _LaneKernels,
                      rounds_remaining_hint as _dense_rounds_remaining_hint)
from .pr_nibble_sparse import check_sparse_eps, pr_nibble_sparse_fixedcap
from .sweep import sweep_cut_sparse

__all__ = [
    "BatchedSparseDiffusionResult", "BatchedSparseClusterResult",
    "batched_pr_nibble_sparse_fixedcap", "batched_sparse_sweep_cut",
    "batched_cluster_sparse_fixedcap",
    "batched_pr_nibble_sparse", "batched_cluster_sparse",
    "sparse_rows_to_dense", "sparse_lane_footprint", "pick_backend",
    "sparse_rounds_remaining_hint", "sparse_lane_kernels",
]


def sparse_rounds_remaining_hint(iterations, frontier_count,
                                 max_iters: int = 10_000) -> np.ndarray:
    """Pending-rounds estimate for *sparse* PR-Nibble lanes.

    The sparse backend runs the same push rounds as the dense one (only the
    state container differs), so the round-count predictor is shared:
    :func:`repro.core.batched.rounds_remaining_hint` applied to the sparse
    state's ``t`` / ``frontier.count``.  Exposed here so the scheduler's
    cost model has one obvious import per backend.
    """
    return _dense_rounds_remaining_hint(iterations, frontier_count, max_iters)


# ------------------------------------------------------------ jitted kernels

@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("optimized", "cap_f", "cap_e", "cap_v",
                                    "max_iters", "backend"))
def batched_pr_nibble_sparse_fixedcap(graph: CSRGraph, seeds, eps, alpha,
                                      optimized: bool, cap_f: int, cap_e: int,
                                      cap_v: int, max_iters: int = 10_000,
                                      *, backend: str = "xla"):
    """vmap of :func:`pr_nibble_sparse_fixedcap`: seeds[B], per-seed (ε, α).

    Shapes: ``seeds`` int32[B], ``eps``/``alpha`` f32[B].  Returns a
    :class:`PRNibbleSparseResult` with a leading [B] axis on every leaf:
    ``p``/``r`` are SparseVecs with ``ids`` int32[B, cap_v] (sorted,
    sentinel-``n``-padded), ``vals`` f32[B, cap_v], ``count`` int32[B];
    ``iterations``/``pushes`` int32[B]; ``overflow`` bool[B].
    """
    def one(s, e, a):
        return pr_nibble_sparse_fixedcap(graph, s, e, a, optimized,
                                         cap_f, cap_e, cap_v, max_iters,
                                         backend=backend)
    return jax.vmap(one)(seeds, eps, alpha)


@functools.partial(jax.jit, static_argnums=(4,),
                   static_argnames=("cap_e", "backend"))
def batched_sparse_sweep_cut(graph: CSRGraph, ids, vals, nnz, cap_e: int, *,
                             backend: str = "xla"):
    """vmap of :func:`sweep_cut_sparse` over B sparse diffusion vectors.

    Shapes: ``ids`` int32[B, cap_n] (sentinel ``n`` beyond each lane's
    ``nnz``), ``vals`` f32[B, cap_n], ``nnz`` int32[B]; ``cap_e`` static.
    Returns a :class:`SweepResult` with leading [B] axis — per-lane live
    memory O(cap_n + cap_e), never O(n).
    """
    def one(i, v, c):
        return sweep_cut_sparse(graph, i, v, c, cap_e, backend=backend)
    return jax.vmap(one)(ids, vals, nnz)


class _SparseClusterLanes(NamedTuple):
    """Per-lane output of the fused sparse diffusion+sweep kernel."""
    conductance: jnp.ndarray       # f32[B, cap_v] — full sweep curve
    best_conductance: jnp.ndarray  # f32[B]
    best_size: jnp.ndarray         # int32[B]
    best_volume: jnp.ndarray       # int32[B]
    order: jnp.ndarray             # int32[B, cap_v] — sweep order (cluster prefix)
    support: jnp.ndarray           # int32[B] — nnz of the diffusion
    pushes: jnp.ndarray            # int32[B]
    iterations: jnp.ndarray        # int32[B]
    overflow: jnp.ndarray          # bool[B] — diffusion OR sweep overflow


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("optimized", "cap_f", "cap_e", "cap_v",
                                    "sweep_cap_e", "backend"))
def batched_cluster_sparse_fixedcap(graph: CSRGraph, seeds, eps, alpha,
                                    optimized: bool, cap_f: int, cap_e: int,
                                    cap_v: int, sweep_cap_e: int, *,
                                    backend: str = "xla"
                                    ) -> _SparseClusterLanes:
    """Fused sparse PR-Nibble + sparse sweep per seed — no dense vector ever.

    The sweep grid is the diffusion's own ``cap_v`` (support ≤ cap_v by
    construction, so the sweep itself cannot truncate support).  Shapes as in
    :func:`batched_pr_nibble_sparse_fixedcap`; the sweep curve is
    f32[B, cap_v] (inf-padded past each lane's support).
    """
    def one(s, e, a):
        res = pr_nibble_sparse_fixedcap(graph, s, e, a, optimized,
                                        cap_f, cap_e, cap_v, backend=backend)
        sw = sweep_cut_sparse(graph, res.p.ids, res.p.vals, res.p.count,
                              sweep_cap_e, backend=backend)
        return _SparseClusterLanes(
            conductance=sw.conductance,
            best_conductance=sw.best_conductance,
            best_size=sw.best_size,
            best_volume=sw.best_volume,
            order=sw.order,
            support=sw.nnz,
            pushes=res.pushes,
            iterations=res.iterations,
            overflow=res.overflow | sw.overflow,
        )
    return jax.vmap(one)(seeds, eps, alpha)


# ------------------------------------------------- host drivers (per-seed retry)

class BatchedSparseDiffusionResult(NamedTuple):
    """Host-side batched sparse diffusion output.

    The sparse columns are ``max(cap_v over dispatched buckets)`` wide:
    lanes served by smaller buckets keep sentinel/zero padding past their
    ``count``.  ``buckets`` entries are (batch, cap_f, cap_e, cap_v).
    """
    p_ids: np.ndarray       # int32[B, capV] — sorted, sentinel-n-padded
    p_vals: np.ndarray      # f32[B, capV]
    p_count: np.ndarray     # int32[B]
    r_ids: np.ndarray       # int32[B, capV]
    r_vals: np.ndarray      # f32[B, capV]
    r_count: np.ndarray     # int32[B]
    iterations: np.ndarray  # int32[B]
    pushes: np.ndarray      # int32[B]
    overflow: np.ndarray    # bool[B] — True only if max_cap_e was exhausted
    buckets: Tuple[Tuple[int, int, int, int], ...]


class BatchedSparseClusterResult(NamedTuple):
    """Host-side fused sparse cluster output.

    Sweep curves are reported on the fixed grid of the *first* bucket's
    ``cap_v`` (same convention as ``batched_cluster``) so NCP accumulators
    see one consistent size axis.
    """
    conductance: np.ndarray       # f32[B, cap_v0]
    best_conductance: np.ndarray  # f32[B]
    best_size: np.ndarray         # int32[B]
    best_volume: np.ndarray       # int32[B]
    support: np.ndarray           # int32[B]
    pushes: np.ndarray            # int32[B]
    iterations: np.ndarray        # int32[B]
    overflow: np.ndarray          # bool[B]
    buckets: Tuple[Tuple[int, int, int, int], ...]


def _grow_sparse_out(out: dict, cap_v: int, n: int) -> None:
    """Widen the (ids, vals) output columns to ``cap_v`` when the ladder
    promotes — already-written lanes keep their data, the new tail is
    sentinel/zero padding."""
    have = out["p_ids"].shape[1]
    if have >= cap_v:
        return
    pad = cap_v - have
    for name in ("p_ids", "r_ids"):
        out[name] = np.pad(out[name], ((0, 0), (0, pad)), constant_values=n)
    for name in ("p_vals", "r_vals"):
        out[name] = np.pad(out[name], ((0, 0), (0, pad)))


def batched_pr_nibble_sparse(graph: CSRGraph, seeds, eps=1e-7, alpha=0.01,
                             optimized: bool = True, cap_f: int = 1 << 10,
                             cap_e: int = 1 << 14, cap_v: int = 1 << 12,
                             max_cap_e: int = 1 << 26,
                             max_iters: int = 10_000, backend: str = "xla"
                             ) -> BatchedSparseDiffusionResult:
    """Batched bucketed sparse driver: per-seed overflow retry on the
    (cap_f, cap_e, cap_v) ladder.  Per-seed output is bit-identical to
    looping :func:`repro.core.pr_nibble_sparse.pr_nibble_sparse` (same
    capacity schedule, same round function).

    ``seeds`` int-like[B] (scalars broadcast); ``eps``/``alpha`` broadcast to
    f32[B].  See :class:`BatchedSparseDiffusionResult` for output shapes.
    """
    graph = _ops.local_csr(graph)   # any graph-like (GraphHandle ok)
    seeds, B, eps, alpha = _prep_batch(seeds, eps, alpha)
    check_sparse_eps(eps)
    n = graph.n
    out = dict(p_ids=np.full((B, cap_v), n, np.int32),
               p_vals=np.zeros((B, cap_v), np.float32),
               p_count=np.zeros(B, np.int32),
               r_ids=np.full((B, cap_v), n, np.int32),
               r_vals=np.zeros((B, cap_v), np.float32),
               r_count=np.zeros(B, np.int32),
               iterations=np.zeros(B, np.int32),
               pushes=np.zeros(B, np.int32))
    ovf = np.zeros(B, bool)
    lad = _CapLadder(n, cap_f, cap_e, max_cap_e, cap_v=cap_v)

    def dispatch(sel):
        _grow_sparse_out(out, lad.cap_v, n)
        res = batched_pr_nibble_sparse_fixedcap(
            graph, jnp.asarray(seeds[sel]), jnp.asarray(eps[sel]),
            jnp.asarray(alpha[sel]), optimized, lad.cap_f, lad.cap_e,
            lad.cap_v, max_iters, backend=backend)
        fields = dict(p_ids=res.p.ids, p_vals=res.p.vals, p_count=res.p.count,
                      r_ids=res.r.ids, r_vals=res.r.vals, r_count=res.r.count,
                      iterations=res.iterations, pushes=res.pushes,
                      overflow=res.overflow)
        return fields, (sel.size, lad.cap_f, lad.cap_e, lad.cap_v)

    buckets = _bucketed_retry(B, dispatch, lad.advance, lad.exhausted, out, ovf)
    return BatchedSparseDiffusionResult(overflow=ovf, buckets=buckets, **out)


def batched_cluster_sparse(graph: CSRGraph, seeds, eps=1e-6, alpha=0.01,
                           optimized: bool = True, cap_f: int = 1 << 10,
                           cap_e: int = 1 << 14, cap_v: int = 1 << 12,
                           sweep_cap_e: int = 1 << 18,
                           max_cap_e: int = 1 << 26, backend: str = "xla"
                           ) -> BatchedSparseClusterResult:
    """Batched fused sparse diffusion + sparse sweep with per-seed retry on
    *any* workspace (cap_f, cap_e, cap_v, sweep_cap_e) overflowing.

    Sweep curves are reported on the first bucket's ``cap_v`` grid (retried
    lanes' longer curves are truncated to it, matching ``batched_cluster``).
    """
    graph = _ops.local_csr(graph)   # any graph-like (GraphHandle ok)
    seeds, B, eps, alpha = _prep_batch(seeds, eps, alpha)
    check_sparse_eps(eps)
    n = graph.n
    out = dict(conductance=np.full((B, cap_v), np.inf, np.float32),
               best_conductance=np.full(B, np.inf, np.float32),
               best_size=np.zeros(B, np.int32),
               best_volume=np.zeros(B, np.int32),
               support=np.zeros(B, np.int32),
               pushes=np.zeros(B, np.int32),
               iterations=np.zeros(B, np.int32))
    ovf = np.zeros(B, bool)
    lad = _CapLadder(n, cap_f, cap_e, max_cap_e, cap_v=cap_v,
                     sweep_cap_e=sweep_cap_e)

    def dispatch(sel):
        res = batched_cluster_sparse_fixedcap(
            graph, jnp.asarray(seeds[sel]), jnp.asarray(eps[sel]),
            jnp.asarray(alpha[sel]), optimized, lad.cap_f, lad.cap_e,
            lad.cap_v, lad.sweep_cap_e, backend=backend)
        fields = res._asdict()
        fields.pop("order")            # not part of the host result
        return fields, (sel.size, lad.cap_f, lad.cap_e, lad.cap_v)

    buckets = _bucketed_retry(B, dispatch, lad.advance, lad.exhausted, out, ovf)
    return BatchedSparseClusterResult(overflow=ovf, buckets=buckets, **out)


# -------------------------------------------------- accounting / backend pick

def sparse_rows_to_dense(ids, vals, count, n: int) -> np.ndarray:
    """Densify host-side sparse rows: f32[B, n] from int32[B, capV] ids +
    f32[B, capV] vals + int32[B] counts (test/cross-check helper)."""
    ids = np.atleast_2d(np.asarray(ids))
    vals = np.atleast_2d(np.asarray(vals))
    count = np.atleast_1d(np.asarray(count))
    B, capv = ids.shape
    dense = np.zeros((B, n), np.float32)
    for b in range(B):
        k = int(count[b])
        dense[b, ids[b, :k]] = vals[b, :k]
    return dense


def sparse_lane_footprint(cap_f: int, cap_e: int, cap_v: int) -> dict:
    """Per-lane live-value accounting for one sparse lane (32-bit slots).

    ``state`` is what persists across rounds (p and r: ids + vals each);
    ``transient`` is the per-round peak extra (frontier ids, edge-batch
    (slot, src, dst), and the ~2(cap_v+cap_e) sort-merge scratch of
    ``sv_merge_add``).  The point of the backend: ``state`` is 4·cap_v —
    bounded by the lane's K, independent of n — while a dense lane's state
    is 2·n.
    """
    state = 4 * cap_v
    transient = cap_f + 3 * cap_e + 2 * (cap_v + cap_e)
    return dict(state=state, transient=transient, total=state + transient)


def pick_backend(n: int, cap_v: int, ratio: int = 4, *,
                 num_shards: int = 1,
                 chip_budget: int | None = None) -> str:
    """Lane-backend heuristic used by ``LocalClusterEngine``.

    Dense vs sparse: a dense lane persists 2·n values (p, r); a sparse lane
    persists 4·cap_v slots plus sort-merge scratch and pays an O(log cap_v)
    factor on every lookup.  Choose sparse only when the dense state is at
    least ``ratio``× the sparse state: n ≥ 2·ratio·cap_v.

    Fits-on-chip: when the graph is sharded (``num_shards > 1``) and a
    ``chip_budget`` (bytes) is given, a query whose dense per-lane state
    2·4·n would blow the budget is routed to the distributed lanes
    (``"dist"``) — the state then lives sharded, O(n/D) per chip.  With no
    budget configured the local heuristic applies unchanged (sharding alone
    never forces the slower multi-chip rounds onto a graph that fits).
    Requests can always pin a backend explicitly (``ClusterRequest.backend``).
    """
    if num_shards > 1 and chip_budget is not None and 8 * n > chip_budget:
        return "dist"
    return "sparse" if n >= 2 * ratio * cap_v else "dense"


# ------------------------------------------- executable-shaped lane kernels

@functools.lru_cache(maxsize=None)
def sparse_lane_kernels(n: int, statics: tuple, cap_f: int, cap_v: int,
                        cap_e: int, sweep_cap_e: int, rounds: int,
                        backend: str) -> _LaneKernels:
    """Sparse-lane kernel bundle for the serving engine — the SparseVec
    analogue of :func:`repro.core.batched.dense_lane_kernels` (same
    ``LaneKernels`` signature set, same donation/AOT contract).  The sweep
    kernel gathers only the finished lane's ``(ids, vals, count)`` support
    — O(cap_v), never O(n) — before running the sparse Theorem-1 sweep, so
    harvests copy support, not pool state.  ``statics = (optimized, β)``
    with β fixed at 1.0 (sparse lanes serve plain PR-Nibble only)."""
    from .pr_nibble_sparse import (pr_nibble_sparse_init,
                                   pr_nibble_sparse_round,
                                   pr_nibble_sparse_alive)
    optimized, _beta = statics
    seed_init = lambda s: pr_nibble_sparse_init(s, n, cap_f, cap_v)

    def init(seeds):
        return jax.vmap(seed_init)(seeds)

    def inject(state, lane, seed):
        return jax.tree.map(lambda buf, v: buf.at[lane].set(v),
                            state, seed_init(seed))

    def step(graph, state, eps, alpha, active):
        def one(s, e, a, act):
            def cond(c):
                s2, k = c
                return act & (k < rounds) & pr_nibble_sparse_alive(s2, 10_000)

            def body(c):
                s2, k = c
                return (pr_nibble_sparse_round(graph, s2, e, a, optimized,
                                               cap_e, backend),
                        k + 1)

            s2, _ = jax.lax.while_loop(cond, body,
                                       (s, jnp.asarray(0, jnp.int32)))
            return s2
        return jax.vmap(one)(state, eps, alpha, active)

    def status(state):
        fc = state.frontier.count.astype(jnp.int32)
        fin = (fc == 0) | state.overflow | (state.t >= 10_000)
        return jnp.stack([fin.astype(jnp.int32),
                          state.overflow.astype(jnp.int32), fc,
                          state.t.astype(jnp.int32),
                          state.pushes.astype(jnp.int32),
                          jnp.zeros_like(fc),
                          state.edge_work.astype(jnp.int32)])

    def sweep(graph, state, lane):
        sw = sweep_cut_sparse(graph, state.p.ids[lane], state.p.vals[lane],
                              state.p.count[lane], sweep_cap_e,
                              backend=backend)
        meta = jnp.stack([sw.best_size, sw.best_volume, sw.nnz,
                          sw.overflow.astype(jnp.int32)])
        return sw.order, meta, sw.best_conductance

    return _LaneKernels(init, inject, step, status, sweep)

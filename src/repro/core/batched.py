"""Batched multi-seed local clustering (paper §5's outer parallelism axis).

"A straightforward way to use parallelism is to run many local graph
computations independently in parallel" — this module makes that the
first-class path instead of an NCP-only special case.  The fixed-capacity
frontier drivers (:func:`pr_nibble_fixedcap`, :func:`hk_pr_fixedcap`) and the
Theorem-1 sweep cut are vmapped over a ``seeds[B]`` axis with *per-seed*
``(ε, α)`` parameters and *shared* static ``(cap_f, cap_e)`` capacities, so a
whole batch of queries is one XLA dispatch and one compile-cache entry.

XLA's while-loop batching rule masks finished lanes (the carry is
``select(pred, new, old)`` per lane), so each lane's state trajectory is
*identical* to running the single-seed driver — batching changes throughput,
never results.

Overflow keeps the bucketed-recompilation contract of the single-seed
drivers, but per seed: lanes whose frontier or edge workspace overflowed are
repacked into a power-of-two-sized retry batch at the next capacity bucket
(same doubling schedule as :func:`repro.core.pr_nibble.pr_nibble`, so the
per-seed results stay bit-identical to the single-seed path).  The whole
batch therefore compiles at most O(log) distinct bucket shapes, all reused
from the jit cache across calls — the property `LocalClusterEngine`
(serve/cluster_engine.py) builds its compiled-shape LRU on.

Capacity-ladder semantics (shared with core/batched_sparse.py):

  * Every jitted kernel takes *static* capacities; one (batch, caps) tuple
    is one compiled shape ("bucket").  Bucket b has caps ``base << b``.
  * Ladder step (``_CapLadder.advance``): ``cap_f`` and the sparse value
    capacity ``cap_v`` double but clamp at ``n + 1`` (a frontier/support can
    never exceed every vertex + sentinel); ``cap_e`` doubles unclamped until
    ``max_cap_e``; the sweep caps ``cap_n``/``sweep_cap_e`` clamp at
    ``n`` / nothing.  This is verbatim the single-seed drivers' schedule —
    the bit-identity guarantee depends on dispatching the *same* static
    shapes the single-seed retry loop would.
  * Retry contract (``_bucketed_retry``): after each dispatch, lanes whose
    overflow flag is set are repacked (padded to a power of two by cycling
    lanes) and re-dispatched one bucket up; lanes that finish are written
    to the output buffers exactly once.  When the ladder is exhausted
    (``cap_e ≥ max_cap_e``) overflowed lanes are written as-is with their
    flag set, matching the single-seed drivers.
  * Recompile boundary: a fresh (batch_pow2, caps) pair.  A B-seed call
    therefore compiles ≤ O(log B · log(max_cap_e/cap_e)) shapes, all shared
    process-wide through the jit cache.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from . import ops as _ops
from .frontier import next_pow2
from .pr_nibble import MAX_ITERS, pr_nibble_fixedcap
from .hk_pr import hk_pr_fixedcap
from .sweep import sweep_cut_dense

__all__ = [
    "BatchedDiffusionResult", "BatchedClusterResult",
    "batched_pr_nibble_fixedcap", "batched_hk_pr_fixedcap",
    "batched_sweep_cut", "batched_cluster_fixedcap",
    "batched_pr_nibble", "batched_hk_pr", "batched_cluster",
    "rounds_remaining_hint", "hk_rounds_remaining",
    "LaneKernels", "dense_lane_kernels", "STATUS_ROWS",
    "STATUS_FINISHED", "STATUS_OVERFLOW", "STATUS_FRONTIER",
    "STATUS_ITER", "STATUS_PUSHES", "STATUS_EXCHANGED", "STATUS_EDGES",
]


# ----------------------------------------------- scheduler cost-model hints

def rounds_remaining_hint(iterations, frontier_count,
                          max_iters: int = MAX_ITERS) -> np.ndarray:
    """Per-lane pending-push-rounds estimate for latency-aware schedulers.

    PR-Nibble has no closed-form round count — termination depends on how the
    residual drains — so the serving scheduler (serve/scheduler.py) needs a
    cheap host-side predictor to turn "EMA tick cost" into "estimated time to
    finish".  This uses two observables of the lane state:

      * ``frontier_count == 0`` → the lane is finished: 0 rounds remain.
      * otherwise, a survival ("Lindy") estimate: a run that has already
        pushed ``t`` rounds is expected to push about ``t`` more, clamped to
        ``[1, max_iters - t]``.  Push-round counts across seeds are
        heavy-tailed (the NCP sweeps make this visible), where this estimator
        is the right crude prior; it deliberately under-promises early
        (t small → short estimate, refined every tick as t grows).

    Vectorized over lanes: ``iterations`` / ``frontier_count`` are int-like
    [B] (scalars broadcast); returns int64[B] estimated rounds remaining.
    This is a *hint* — scheduling consumes it, results never depend on it.
    """
    it = np.atleast_1d(np.asarray(iterations, np.int64))
    fc = np.atleast_1d(np.asarray(frontier_count, np.int64))
    rem = np.clip(it, 1, np.maximum(max_iters - it, 1))
    return np.where(fc > 0, rem, 0)


def hk_rounds_remaining(j, done, frontier_count, N: int) -> np.ndarray:
    """Exact pending-rounds count for HK-PR lanes: the rounds are Taylor
    levels, so an alive lane at level ``j`` has exactly ``N - j`` left
    (0 when ``done`` or the frontier emptied).  Same [B] conventions as
    :func:`rounds_remaining_hint`."""
    j = np.atleast_1d(np.asarray(j, np.int64))
    done = np.atleast_1d(np.asarray(done, bool))
    fc = np.atleast_1d(np.asarray(frontier_count, np.int64))
    return np.where(done | (fc == 0), 0, np.maximum(N - j, 0))


# ------------------------------------------------------------ jitted kernels

@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("optimized", "cap_f", "cap_e",
                                    "max_iters", "beta", "backend"))
def batched_pr_nibble_fixedcap(graph: CSRGraph, seeds, eps, alpha,
                               optimized: bool, cap_f: int, cap_e: int,
                               max_iters: int = MAX_ITERS, beta: float = 1.0,
                               *, backend: str = "xla"):
    """vmap of :func:`pr_nibble_fixedcap`: seeds[B] with per-seed (eps, alpha).

    Shapes: ``seeds`` int32[B], ``eps``/``alpha`` f32[B]; returns a
    :class:`PRNibbleResult` whose leaves carry a leading [B] axis
    (``p``/``r`` f32[B, n], counters int32[B], ``overflow`` bool[B]).
    """
    def one(s, e, a):
        return pr_nibble_fixedcap(graph, s, e, a, optimized, cap_f, cap_e,
                                  max_iters, beta, backend=backend)
    return jax.vmap(one)(seeds, eps, alpha)


@functools.partial(jax.jit, static_argnums=(2, 4, 5, 6),
                   static_argnames=("N", "t", "cap_f", "cap_e", "backend"))
def batched_hk_pr_fixedcap(graph: CSRGraph, seeds, N: int, eps, t: float,
                           cap_f: int, cap_e: int, *, backend: str = "xla"):
    """vmap of :func:`hk_pr_fixedcap`: seeds[B] with per-seed eps (N, t static).

    Shapes: ``seeds`` int32[B], ``eps`` f32[B]; result leaves lead with [B].
    """
    def one(s, e):
        return hk_pr_fixedcap(graph, s, N, e, t, cap_f, cap_e,
                              backend=backend)
    return jax.vmap(one)(seeds, eps)


@functools.partial(jax.jit, static_argnums=(2, 3),
                   static_argnames=("cap_n", "cap_e", "backend"))
def batched_sweep_cut(graph: CSRGraph, p, cap_n: int, cap_e: int, *,
                      backend: str = "xla"):
    """vmap of :func:`sweep_cut_dense` over p[B, n] diffusion vectors.

    ``p`` is f32[B, n]; returns a :class:`SweepResult` with leading [B] axis
    (curves f32[B, min(cap_n, n)], scalars → [B]).  See
    :func:`repro.core.batched_sparse.batched_sparse_sweep_cut` for the
    O(cap_n + cap_e)-per-lane variant that never touches f32[n].
    """
    return jax.vmap(
        lambda q: sweep_cut_dense(graph, q, cap_n, cap_e, backend))(p)


class _ClusterLanes(NamedTuple):
    """Per-lane output of the fused diffusion+sweep kernel."""
    conductance: jnp.ndarray       # f32[B, cap_n] — full sweep curve
    best_conductance: jnp.ndarray  # f32[B]
    best_size: jnp.ndarray         # int32[B]
    best_volume: jnp.ndarray       # int32[B]
    order: jnp.ndarray             # int32[B, cap_n] — sweep order (cluster prefix)
    support: jnp.ndarray           # int32[B] — nnz of the diffusion
    pushes: jnp.ndarray            # int32[B]
    iterations: jnp.ndarray        # int32[B]
    overflow: jnp.ndarray          # bool[B] — diffusion OR sweep overflow


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9),
                   static_argnames=("optimized", "cap_f", "cap_e", "cap_n",
                                    "sweep_cap_e", "beta", "backend"))
def batched_cluster_fixedcap(graph: CSRGraph, seeds, eps, alpha,
                             optimized: bool, cap_f: int, cap_e: int,
                             cap_n: int, sweep_cap_e: int,
                             beta: float = 1.0, *,
                             backend: str = "xla") -> _ClusterLanes:
    """Fused PR-Nibble + sweep cut per seed — the NCP/serving inner kernel.

    Unlike the plain diffusion kernels this never materializes p[B, n] in the
    result: each lane reduces to its sweep curve + summary stats.
    """
    def one(s, e, a):
        res = pr_nibble_fixedcap(graph, s, e, a, optimized, cap_f, cap_e,
                                 MAX_ITERS, beta, backend=backend)
        sw = sweep_cut_dense(graph, res.p, cap_n, sweep_cap_e, backend)
        return _ClusterLanes(
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

class BatchedDiffusionResult(NamedTuple):
    p: np.ndarray           # f32[B, n]
    r: np.ndarray           # f32[B, n] (zeros for HK-PR, which has no residual out)
    iterations: np.ndarray  # int32[B]
    pushes: np.ndarray      # int32[B]
    edge_work: np.ndarray   # int32[B]
    overflow: np.ndarray    # bool[B] — True only if max_cap_e was exhausted
    buckets: Tuple[Tuple[int, int, int], ...]  # (batch, cap_f, cap_e) dispatched


class BatchedClusterResult(NamedTuple):
    conductance: np.ndarray       # f32[B, cap_n] — full sweep curves
    best_conductance: np.ndarray  # f32[B]
    best_size: np.ndarray         # int32[B]
    best_volume: np.ndarray       # int32[B]
    support: np.ndarray           # int32[B]
    pushes: np.ndarray            # int32[B]
    iterations: np.ndarray        # int32[B]
    overflow: np.ndarray          # bool[B]
    buckets: Tuple[Tuple[int, int, int], ...]


def _prep_batch(seeds, *params):
    seeds = np.atleast_1d(np.asarray(seeds, np.int32))
    B = seeds.shape[0]
    out = [np.broadcast_to(np.asarray(p, np.float32), (B,)).astype(np.float32)
           for p in params]
    return (seeds, B, *out)


def _retry_sizes(k: int, B: int) -> int:
    """Retry batches are padded to the next power of two (≤ the original B)
    so the whole run touches at most O(log B · log cap) compiled shapes."""
    return min(next_pow2(max(k, 1)), next_pow2(B))


_annotate = None


def _trace_annotate(name, **attrs):
    """Forward to the serving layer's ambient tracing hook
    (:func:`repro.serve.tracing.annotate`) — a no-op unless a Tracer scope
    is active on this thread.  Imported lazily at first call: core must not
    import ``repro.serve`` at module time (serve imports core back), and the
    serve layer is optional for pure-core users."""
    global _annotate
    if _annotate is None:
        try:
            from repro.serve.tracing import annotate as _annotate
        except Exception:                       # serve layer unavailable
            _annotate = lambda name, **attrs: None
    _annotate(name, **attrs)


def _bucketed_retry(B, dispatch, advance, exhausted, outputs, ovf_out):
    """Shared per-seed retry ladder for the host drivers.

    ``dispatch(sel)`` runs the current capacity bucket for the padded lane
    selection ``sel`` and returns ``(fields, bucket)``: ``fields`` maps each
    output name (plus "overflow") to an np array with leading axis
    ``len(sel)``; ``bucket`` is the (batch, cap_f, cap_e) key recorded for
    the compile-shape accounting.  ``advance()`` doubles the capacities;
    ``exhausted()`` reports the ladder's end (overflowed lanes are then
    written as-is with their flag set, matching the single-seed drivers).
    """
    pending = np.arange(B)
    buckets = []
    while True:
        k = pending.size
        sel = np.resize(pending, _retry_sizes(k, B))  # pad by cycling lanes
        fields, bucket = dispatch(sel)
        buckets.append(bucket)
        o = np.asarray(fields["overflow"])[:k]
        # Paper-native work measures for an active trace scope (serve layer):
        # one event per ladder dispatch — bucket shape, lanes served,
        # overflow count, total pushes, dist exchange volume when present.
        obs = dict(bucket=tuple(int(b) for b in bucket), lanes=int(k),
                   hop=len(buckets) - 1, overflowed=int(o.sum()))
        for extra in ("pushes", "exchanged"):
            if extra in fields:
                obs[extra] = int(np.asarray(fields[extra])[:k].sum())
        _trace_annotate("ladder_dispatch", **obs)
        final = (not o.any()) or exhausted()
        done = pending if final else pending[~o]
        take = slice(None) if final else ~o
        for name, buf in outputs.items():
            vals = np.asarray(fields[name])[:k][take]
            if buf.ndim == 2 and vals.shape[1] != buf.shape[1]:
                m = min(vals.shape[1], buf.shape[1])  # grown sweep grid
                buf[done, :m] = vals[:, :m]
            else:
                buf[done] = vals
        ovf_out[done] = o[take]
        if final:
            return tuple(buckets)
        pending = pending[o]
        advance()


class _CapLadder:
    """The single-seed drivers' doubling schedule, shared by retries.

    Generalized over every per-lane capacity, not just the vertex-count-like
    ones: ``cap_f`` (frontier slots), ``cap_e`` (edge workspace), and
    optionally ``cap_v`` (SparseVec value slots, the sparse backend's K),
    ``cap_n``/``sweep_cap_e`` (sweep grid / sweep edge workspace), and
    ``cap_x`` (the distributed path's per-owner exchange buckets, clamped
    at ``cap_e``).  ``None`` capacities are absent from the schedule.
    """

    def __init__(self, n, cap_f, cap_e, max_cap_e, cap_n=None, sweep_cap_e=None,
                 cap_v=None, cap_x=None):
        self.n, self.cap_f, self.cap_e, self.max_cap_e = n, cap_f, cap_e, max_cap_e
        self.cap_n, self.sweep_cap_e = cap_n, sweep_cap_e
        self.cap_v = cap_v
        self.cap_x = cap_x

    def exhausted(self):
        return self.cap_e >= self.max_cap_e

    def advance(self):
        self.cap_f = min(self.cap_f * 2, self.n + 1)
        self.cap_e = self.cap_e * 2
        if self.cap_v is not None:
            self.cap_v = min(self.cap_v * 2, self.n + 1)
        if self.cap_n is not None:
            self.cap_n = min(self.cap_n * 2, self.n)
        if self.sweep_cap_e is not None:
            self.sweep_cap_e = self.sweep_cap_e * 2
        if self.cap_x is not None:
            # per-owner exchange buckets (distributed path): a bucket can
            # never usefully exceed the edge workspace that fills it
            self.cap_x = min(self.cap_x * 2, self.cap_e)


def batched_pr_nibble(graph: CSRGraph, seeds, eps=1e-7, alpha=0.01,
                      optimized: bool = True, cap_f: int = 1 << 12,
                      cap_e: int = 1 << 16, max_cap_e: int = 1 << 26,
                      beta: float = 1.0, max_iters: int = MAX_ITERS,
                      backend: str = "xla") -> BatchedDiffusionResult:
    """Batched bucketed driver: one dispatch per capacity bucket, per-seed
    overflow retry.  Per-seed output is identical to looping
    :func:`repro.core.pr_nibble.pr_nibble` (same capacity schedule).

    ``seeds`` is int-like[B] (scalars broadcast); ``eps``/``alpha`` broadcast
    to f32[B].  Returns host-side numpy: ``p``/``r`` f32[B, n], counters
    int32[B], ``overflow`` bool[B] (True only if max_cap_e was exhausted),
    and the dispatched ``buckets`` tuple for compile-shape accounting.
    """
    graph = _ops.local_csr(graph)   # any graph-like (GraphHandle ok)
    seeds, B, eps, alpha = _prep_batch(seeds, eps, alpha)
    n = graph.n
    out = dict(p=np.zeros((B, n), np.float32), r=np.zeros((B, n), np.float32),
               iterations=np.zeros(B, np.int32), pushes=np.zeros(B, np.int32),
               edge_work=np.zeros(B, np.int32))
    ovf = np.zeros(B, bool)
    lad = _CapLadder(n, cap_f, cap_e, max_cap_e)

    def dispatch(sel):
        res = batched_pr_nibble_fixedcap(
            graph, jnp.asarray(seeds[sel]), jnp.asarray(eps[sel]),
            jnp.asarray(alpha[sel]), optimized, lad.cap_f, lad.cap_e,
            max_iters, beta, backend=backend)
        return res._asdict(), (sel.size, lad.cap_f, lad.cap_e)

    buckets = _bucketed_retry(B, dispatch, lad.advance, lad.exhausted, out, ovf)
    return BatchedDiffusionResult(overflow=ovf, buckets=buckets, **out)


def batched_hk_pr(graph: CSRGraph, seeds, N: int = 20, eps=1e-7,
                  t: float = 10.0, cap_f: int = 1 << 12, cap_e: int = 1 << 16,
                  max_cap_e: int = 1 << 26,
                  backend: str = "xla") -> BatchedDiffusionResult:
    """Batched bucketed HK-PR driver, mirroring :func:`batched_pr_nibble`."""
    graph = _ops.local_csr(graph)   # any graph-like (GraphHandle ok)
    seeds, B, eps = _prep_batch(seeds, eps)
    n = graph.n
    out = dict(p=np.zeros((B, n), np.float32),
               iterations=np.zeros(B, np.int32), pushes=np.zeros(B, np.int32),
               edge_work=np.zeros(B, np.int32))
    ovf = np.zeros(B, bool)
    lad = _CapLadder(n, cap_f, cap_e, max_cap_e)

    def dispatch(sel):
        res = batched_hk_pr_fixedcap(graph, jnp.asarray(seeds[sel]), N,
                                     jnp.asarray(eps[sel]), t,
                                     lad.cap_f, lad.cap_e, backend=backend)
        return res._asdict(), (sel.size, lad.cap_f, lad.cap_e)

    buckets = _bucketed_retry(B, dispatch, lad.advance, lad.exhausted, out, ovf)
    return BatchedDiffusionResult(r=np.zeros((B, n), np.float32),
                                  overflow=ovf, buckets=buckets, **out)


def batched_cluster(graph: CSRGraph, seeds, eps=1e-6, alpha=0.01,
                    optimized: bool = True, cap_f: int = 1 << 12,
                    cap_e: int = 1 << 16, cap_n: int = 1 << 12,
                    sweep_cap_e: int = 1 << 18, max_cap_e: int = 1 << 26,
                    beta: float = 1.0,
                    backend: str = "xla") -> BatchedClusterResult:
    """Batched PR-Nibble + sweep with per-seed retry on *either* the
    diffusion or sweep workspace overflowing (all capacities double).

    Sweep curves are reported on the fixed ``min(cap_n, n)`` grid of the
    first bucket so the NCP accumulator sees one consistent size axis.
    """
    graph = _ops.local_csr(graph)   # any graph-like (GraphHandle ok)
    seeds, B, eps, alpha = _prep_batch(seeds, eps, alpha)
    n = graph.n
    grid = min(cap_n, n)
    out = dict(conductance=np.full((B, grid), np.inf, np.float32),
               best_conductance=np.full(B, np.inf, np.float32),
               best_size=np.zeros(B, np.int32),
               best_volume=np.zeros(B, np.int32),
               support=np.zeros(B, np.int32),
               pushes=np.zeros(B, np.int32),
               iterations=np.zeros(B, np.int32))
    ovf = np.zeros(B, bool)
    lad = _CapLadder(n, cap_f, cap_e, max_cap_e, cap_n=grid,
                     sweep_cap_e=sweep_cap_e)

    def dispatch(sel):
        res = batched_cluster_fixedcap(
            graph, jnp.asarray(seeds[sel]), jnp.asarray(eps[sel]),
            jnp.asarray(alpha[sel]), optimized, lad.cap_f, lad.cap_e,
            min(lad.cap_n, n), lad.sweep_cap_e, beta, backend=backend)
        fields = res._asdict()
        fields.pop("order")            # not part of the host result
        return fields, (sel.size, lad.cap_f, lad.cap_e)

    buckets = _bucketed_retry(B, dispatch, lad.advance, lad.exhausted, out, ovf)
    return BatchedClusterResult(overflow=ovf, buckets=buckets, **out)


# ------------------------------------------- executable-shaped lane kernels
# The serving engine (serve/cluster_engine.py) steps resident lane pools
# through exactly the round functions above, but needs them packaged as
# *executables*: fixed-signature functions it can jit under the pool's name
# and AOT-lower (.lower().compile()) per pool shape, with the lane state
# donated so a tick updates the pool buffers in place (serve/aot.py does
# both).  These factories are that packaging — one LaneKernels bundle per
# (n, method, statics, caps, rounds, backend) shape, lru_cached so every
# engine instance (and every pool re-creation after LRU eviction) shares one
# set of functions process-wide.

# Row indices of the stacked int32[STATUS_ROWS, B] per-tick status readback
# (LaneKernels.status): ONE device→host transfer carries every observable
# the engine's harvest/scheduler path needs — finished & overflow flags,
# frontier occupancy, iteration counter, push count, (dist lanes only)
# exchanged-pair count, and the lane's running edge work (Σ expanded edges,
# the numerator of the tick's edge-slot use).  Results never depend on these
# being fresh; harvest correctness does, so the engine pulls them once per
# tick, post-step.
(STATUS_FINISHED, STATUS_OVERFLOW, STATUS_FRONTIER,
 STATUS_ITER, STATUS_PUSHES, STATUS_EXCHANGED, STATUS_EDGES) = range(7)
STATUS_ROWS = 7


class LaneKernels(NamedTuple):
    """Fixed-signature tick kernels for one lane-pool shape, as plain
    functions: :func:`repro.serve.aot.compile_lane_executables` jits each
    under the pool's executable name and donates the state of ``inject``
    and ``step``.

    ``init(seeds[B]) → state`` (vmapped placeholder build);
    ``inject(state, lane, seed) → state`` (``state`` donated);
    ``step(graph, state, eps[B], alpha[B], active[B]) → state`` (``state``
    donated; ``alpha`` is ignored by HK-PR but kept in the signature so
    every pool shares one calling convention);
    ``status(state) → int32[STATUS_ROWS, B]`` (the coalesced readback);
    ``sweep(graph, state, lane) → (order, meta_i32[4], φ)`` — the
    harvest-gather: slice one finished lane's diffusion out of the pool and
    sweep it on-device, returning only ``order`` (int32[cap_n] / [cap_v]),
    ``meta = [best_size, best_volume, nnz, overflow]`` and the best
    conductance — never the full pool state.
    """
    init: object
    inject: object
    step: object
    status: object
    sweep: object


@functools.lru_cache(maxsize=None)
def dense_lane_kernels(n: int, method: str, statics: tuple, cap_f: int,
                       cap_e: int, cap_n: int, sweep_cap_e: int,
                       rounds: int, backend: str) -> LaneKernels:
    """Dense-lane kernel bundle: PR-Nibble (``statics = (optimized, β)``)
    or HK-PR (``statics = (N, t)``) over f32[n] state rows.  The step body
    is the same masked while-loop the batched drivers run, so a lane's
    trajectory is bit-identical to the single-seed driver's (guarantee #2);
    donation and AOT lowering change where buffers live, never values
    (guarantee #9)."""
    from .pr_nibble import pr_nibble_init, pr_nibble_round, pr_nibble_alive
    from .hk_pr import hk_pr_init, hk_pr_round, hk_pr_alive
    if method == "pr_nibble":
        optimized, beta = statics
        seed_init = lambda s: pr_nibble_init(s, n, cap_f)
        alive = lambda s: pr_nibble_alive(s, MAX_ITERS)
        rnd = lambda g, s, e, a: pr_nibble_round(g, s, e, a, optimized,
                                                 cap_e, beta, backend)
        iter_of = lambda s: s.t
        done_of = lambda s: jnp.zeros_like(s.overflow)
    elif method == "hk_pr":
        N, t = statics
        seed_init = lambda s: hk_pr_init(s, n, cap_f)
        alive = hk_pr_alive
        rnd = lambda g, s, e, a: hk_pr_round(g, s, N, e, t, cap_e, backend)
        iter_of = lambda s: s.j
        done_of = lambda s: s.done
    else:
        raise ValueError(f"unknown method: {method!r}")

    def init(seeds):
        return jax.vmap(seed_init)(seeds)

    def inject(state, lane, seed):
        return jax.tree.map(lambda buf, v: buf.at[lane].set(v),
                            state, seed_init(seed))

    def step(graph, state, eps, alpha, active):
        def one(s, e, a, act):
            def cond(c):
                s2, k = c
                return act & (k < rounds) & alive(s2)

            def body(c):
                s2, k = c
                return rnd(graph, s2, e, a), k + 1

            s2, _ = jax.lax.while_loop(cond, body,
                                       (s, jnp.asarray(0, jnp.int32)))
            return s2
        return jax.vmap(one)(state, eps, alpha, active)

    def status(state):
        fc = state.frontier.count.astype(jnp.int32)
        fin = ((fc == 0) | state.overflow | done_of(state)
               | (iter_of(state) >= MAX_ITERS))
        return jnp.stack([fin.astype(jnp.int32),
                          state.overflow.astype(jnp.int32), fc,
                          iter_of(state).astype(jnp.int32),
                          state.pushes.astype(jnp.int32),
                          jnp.zeros_like(fc),
                          state.edge_work.astype(jnp.int32)])

    def sweep(graph, state, lane):
        sw = sweep_cut_dense(graph, state.p[lane], cap_n, sweep_cap_e,
                             backend)
        meta = jnp.stack([sw.best_size, sw.best_volume, sw.nnz,
                          sw.overflow.astype(jnp.int32)])
        return sw.order, meta, sw.best_conductance

    return LaneKernels(init, inject, step, status, sweep)

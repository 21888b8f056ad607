"""Local clustering as a service: continuous batching over seed queries.

``LocalClusterEngine`` is the graph-query analogue of ``engine.py``'s
``batched_serve``: a queue of :class:`ClusterRequest`\\ s (seed, α, ε, method)
is packed into a fixed number of batch *lanes*; every scheduler tick advances
all active lanes a bounded number of push rounds through one jitted kernel,
finished lanes are harvested (swept for their best cut) and immediately
refilled from the queue — *without recompiling*, because lane count and
frontier capacities are static shapes and refill is a dynamic-index
injection into the batched state.

Requests with heterogeneous (α, ε) share one lane pool; only genuinely
trace-level choices (method, update rule, β, HK's (N, t)), the lane
*backend* (dense vs sparse state), and the capacity *bucket* select a pool.
Lanes that overflow their bucket's workspace are re-enqueued one
power-of-two bucket up (the bucketed recompilation contract of
core/frontier.py), so a request stream compiles at most O(log) distinct
shapes per (method, backend).  Idle pools beyond ``lru_pools`` are evicted
least-recently-used to bound device memory; the engine's
:class:`~repro.serve.aot.ExecutableCache` keeps the AOT-compiled tick
programs, so re-creating an evicted pool never re-traces.

Hot path
--------
Local (dense/sparse) pools run entirely through ahead-of-time-compiled
executables (serve/aot.py): every tick entry point — init, inject, step,
status, harvest-gather sweep — is ``jit(...).lower(...).compile()``'d once
per pool key (eagerly via :meth:`LocalClusterEngine.warmup`, else at first
pool creation), with the lane state **donated** on inject/step so pool
buffers update in place.  A tick pays exactly **one** device→host sync: the
stacked int32[7, B] status readback (finished / overflow / frontier / iters
/ pushes / exchanged / edge work), mirrored host-side and consumed by
harvest, the finalize counters, the tick's edge-slot counters, the
scheduler's pending-rounds hints, and trace annotations alike.  Harvest
copies a finished lane's *support* (order buffer + 4 counters + φ), never
pool state.  In front of it all sits a versioned seed→result LRU
(serve/result_cache.py): a repeated query resolves at submit in O(1), keyed
on the handle's graph version so edge mutations invalidate wholesale.  None of this changes answers — AOT lowering,
donation, coalesced readbacks, and caching move bytes and compile time,
never values (docs/algorithms.md, guarantee #9).

Backends
--------
``backend="dense"`` lanes carry f32[n] state vectors (fast lookups, memory
O(n) per lane).  ``backend="sparse"`` lanes carry :class:`SparseVec`
``(ids, vals)`` pairs of capacity ``cap_v`` — per-lane live state O(cap_v),
independent of n — and are harvested with the sparse sweep
(:func:`repro.core.sweep.sweep_cut_sparse`), so a sparse request never
materializes a dense vector anywhere on its path.  ``backend="dist"`` lanes (available when the
engine's :class:`~repro.graphs.handle.GraphHandle` is sharded) carry their
state *sharded over the mesh's data axis* — [B, n/D] per chip — and step
through the shard_map'd round kernels of :mod:`repro.core.batched_dist`
(one bucketed all_to_all per round for the whole pool); dist pools are keyed
on the shard topology (axis, D), so two meshes never share a compiled shape.
``backend="auto"`` (default) picks per request via
:func:`repro.core.batched_sparse.pick_backend` (sparse iff n ≥ 2·ratio·cap_v;
dist iff the graph is sharded and the dense lane state would blow
``dist_chip_budget``); a request can pin its lane type with
``ClusterRequest.backend``.  The sparse and dist states exist only for plain
PR-Nibble (β = 1): HK-PR or β-selection requests always serve dense.

Orthogonal to the lane type is the *kernel* backend
(``ops_backend="xla" | "pallas" | "auto"``, engine-wide or per request via
``ClusterRequest.ops_backend``): which implementation every scatter/merge/
scan inside the rounds dispatches to (:mod:`repro.core.ops`); "auto" is
"xla" on every platform.  The kernel backend is pool-key and result-cache
material: the backends agree bit for bit where XLA folds in update order
(guarantee #6), which is shown on the CPU, not promised everywhere.

Scheduling surface
------------------
The engine itself is a *drain-oriented* batcher; the asynchronous,
deadline-aware layer lives above it in serve/scheduler.py
(``AsyncClusterEngine``).  What this module exposes for that layer:
per-pool stepping (:meth:`LocalClusterEngine.tick_pool` — one refill →
step → harvest pass of a single pool, wall-time measured and folded into
the pool's ``cost_ema``), pool observables (``occupancy``, ``tickets``,
``pending_rounds``/``pending_ticks`` built on the batched layers'
rounds-remaining hints), partial harvest for deadline expiry
(:meth:`LocalClusterEngine.harvest_partial` → ``deadline_missed=True``
results), and batch result pickup (:meth:`LocalClusterEngine.take_completed`).
Scheduling never changes answers: any interleaving of ``tick_pool`` calls
steps each lane through the same round function in the same order, so a
scheduled request's result is bit-identical to ``run()``'s.

Capacity-ladder / retry contract: buckets follow the single-seed drivers'
doubling schedule (cap_f, cap_v clamped at n+1; cap_e unclamped to
``max_cap_e``; sweep caps likewise), so a request promoted b buckets up
computes bit-identically to the single-seed driver retrying b times.
Recompile boundary: (method, backend, statics, ops_backend, bucket, topo) ×
batch_slots — ``topo`` is the shard topology (mesh axis, shard count) for
dist pools, None for local ones; all dynamic knobs (seed, α, ε, lane
occupancy) move through traced values.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from repro.graphs.handle import GraphHandle, as_handle
from repro.core import ops as core_ops
from repro.core.batched_dist import dist_lane_kernels
from repro.core.pr_nibble import MAX_ITERS
from repro.core.pr_nibble_sparse import check_sparse_eps
from repro.core.sweep import sweep_cut_dense, sweep_cut_sparse
from repro.core.batched import (STATUS_EDGES, STATUS_EXCHANGED,
                                STATUS_FINISHED, STATUS_FRONTIER, STATUS_ITER,
                                STATUS_OVERFLOW, STATUS_PUSHES,
                                dense_lane_kernels, hk_rounds_remaining,
                                rounds_remaining_hint)
from repro.core.batched_sparse import pick_backend, sparse_lane_kernels
from repro.serve.aot import ExecutableCache, compile_lane_executables
from repro.serve.result_cache import ResultCache, result_key
from repro.serve.telemetry import EMA, pool_label
from repro.serve.tracing import RequestTrace, Tracer, watch_compiles

__all__ = ["ClusterRequest", "ClusterResult", "LocalClusterEngine",
           "UnknownTicket"]


class UnknownTicket(KeyError):
    """Raised by :meth:`LocalClusterEngine.result` / :meth:`peek` for a
    ticket this engine never issued, or whose result was already consumed."""


@dataclasses.dataclass(frozen=True)
class ClusterRequest:
    """One local-clustering query: which seed, which diffusion, which knobs."""
    seed: int
    alpha: float = 0.01        # PR-Nibble teleport
    eps: float = 1e-6          # approximation / truncation threshold
    method: str = "pr_nibble"  # "pr_nibble" | "hk_pr"
    optimized: bool = True     # PR-Nibble update rule (Fig 3 vs Fig 4)
    beta: float = 1.0          # PR-Nibble top-β round selection
    N: int = 10                # HK-PR Taylor degree
    t: float = 5.0             # HK-PR temperature
    backend: Optional[str] = None  # None = engine default; "dense" | "sparse"
    ops_backend: Optional[str] = None  # None = engine default; "xla" |
    #   "pallas" | "auto" — kernel backend (repro.core.ops), orthogonal to
    #   the dense/sparse lane choice ("auto" is "xla")
    # Scheduling hints, consumed by serve/scheduler.py's AsyncClusterEngine
    # (the synchronous engine ignores them).  Never part of a pool key:
    # deadlines/priorities order work, they never select a compiled program.
    deadline_ms: Optional[float] = None  # latency budget from submission;
    #   None = best effort (no deadline)
    priority: int = 0          # higher = more urgent among undeadlined work


@dataclasses.dataclass
class ClusterResult:
    request: ClusterRequest
    conductance: float         # φ of the best sweep prefix
    size: int                  # |S*|
    volume: int                # vol(S*)
    support: int               # nnz of the diffusion vector
    cluster: np.ndarray        # int32[size] — member vertex ids
    pushes: int
    iterations: int
    bucket: int                # capacity bucket that served the request
    overflow: bool             # True only if every bucket overflowed
    backend: str = "dense"     # lane type that served the request
    ops_backend: str = "xla"   # kernel backend that served the request
    deadline_missed: bool = False  # True: the deadline expired and this is a
    #   best-effort partial harvest (or a completed-but-late delivery), not
    #   the converged diffusion


# --------------------------------------------------------------- tick kernels
# Local (dense/sparse) pools step through AOT-compiled executables: the
# LaneKernels factories of core/batched.py / core/batched_sparse.py are
# lowered+compiled per pool key by the engine's ExecutableCache
# (serve/aot.py), with the lane state donated — see LocalClusterEngine.
# Dist pools keep their shard_map'd jits (repro.core.batched_dist, lru_cached
# per topology); only their coalesced status readback lives here.

@jax.jit
def _dist_status(front, t, pushes, overflow, exchanged, edge_work):
    """Stacked int32[7, B] status readback for dist lanes — the replicated
    per-lane scalars of DistLaneState, in the STATUS_* row order of
    repro.core.batched, so one transfer serves harvest, the scheduler's
    pending-rounds hints, and the trace annotations."""
    i32 = lambda x: x.astype(jnp.int32)
    fin = (front == 0) | overflow | (t >= MAX_ITERS)
    return jnp.stack([i32(fin), i32(overflow), i32(front), i32(t),
                      i32(pushes), i32(exchanged), i32(edge_work)])


# ----------------------------------------------------------------- lane pool

class _Pool:
    """Fixed-shape lane pool for one (method, backend, statics, ops_backend,
    bucket, topo) key.  ``topo`` is None for local (dense/sparse) pools and
    the (mesh axis, shard count) pair for ``dist`` pools — shard topology is
    pool-key material because it selects a different compiled SPMD program."""

    def __init__(self, engine: "LocalClusterEngine", key: tuple):
        method, backend, statics, ops_backend, bucket, topo = key
        self.engine = engine
        self.key = key
        self.method = method
        self.backend = backend
        self.ops_backend = ops_backend
        self.statics = statics
        self.bucket = bucket
        self.topo = topo
        caps = engine._pool_caps(key)
        self.cap_f = caps["cap_f"]
        self.cap_e = caps["cap_e"]
        self.cap_n = caps["cap_n"]
        self.sweep_cap_e = caps["sweep_cap_e"]
        self.cap_v = caps["cap_v"]
        B = engine.batch_slots
        # lanes start inactive; injected states overwrite these placeholders
        # (host seeds: building them with jnp would compile after warmup)
        seeds = np.zeros(B, np.int32)
        if backend == "dist":
            pg = engine.handle.partitioned()
            mesh = engine.handle.require_mesh()
            self.cap_x = caps["cap_x"]
            optimized, _beta = statics
            self._dist_init, self._dist_inject, self._dist_step_for = \
                dist_lane_kernels(mesh, engine.handle.axis, pg.rows_per,
                                  self.cap_f, self.cap_e, self.cap_x,
                                  optimized, ops_backend)
            self.exec = None    # dist pools step through the shard_map jits
            self.state = self._dist_init(seeds)
            # each shard expands into its own cap_e edge workspace
            self.edge_slots_per_round = self.cap_e * topo[1]
        else:
            # AOT executables from the engine's cache: a re-created pool
            # (after LRU eviction) or a ladder hop re-uses the compiled
            # programs — pool construction never re-traces after warmup
            self.exec = engine._executables_for(key)
            self.state = self.exec.init(seeds)
            self.edge_slots_per_round = self.cap_e
        self.eps = np.zeros(B, np.float32)
        self.alpha = np.zeros(B, np.float32)
        self.lane: List[Optional[Tuple[int, ClusterRequest]]] = [None] * B
        self.queue: deque = deque()
        # Host mirror of the tick's coalesced status readback
        # (int32[STATUS_ROWS, B]): written once per tick by harvest's single
        # device→host sync, patched host-side on inject, consumed by
        # finalize (pushes/iterations/overflow) and the scheduler hints
        # (pending_rounds) — nothing else re-syncs.
        self._status_host: Optional[np.ndarray] = None
        # Cost-model observables (serve/scheduler.py): EMA of measured tick
        # wall time, fed by LocalClusterEngine.tick_pool.  None until the
        # first tick.  Same telemetry.EMA the registry exports, so alpha is
        # configured in exactly one place (engine.cost_ema_alpha).
        self._cost = EMA(engine.cost_ema_alpha)
        self.ticks = 0
        engine.stats["pools_created"] += 1
        engine.stats["bucket_shapes"].add(
            (method, backend, ops_backend, B, self.cap_f, self.cap_e, topo))

    def has_work(self) -> bool:
        return bool(self.queue) or any(l is not None for l in self.lane)

    # -- scheduler observables ----------------------------------------------

    def note_tick(self, seconds: float) -> None:
        """Fold one measured refill+step+harvest wall time into the EMA."""
        self.ticks += 1
        self._cost.update(seconds)

    @property
    def cost_ema(self) -> Optional[float]:
        """EMA of measured tick wall time (None before the first tick)."""
        return self._cost.value

    def occupancy(self) -> int:
        """Active lanes (injected, not yet harvested)."""
        return sum(l is not None for l in self.lane)

    def tickets(self) -> List[int]:
        """Every ticket resident in this pool: active lanes, then queued."""
        out = [slot[0] for slot in self.lane if slot is not None]
        out.extend(idx for idx, _ in self.queue)
        return out

    def pending_rounds(self) -> np.ndarray:
        """Estimated push rounds remaining per active lane (0 for idle
        lanes).  PR-Nibble lanes (dense, sparse, or dist — same round
        structure) use the survival hint
        :func:`repro.core.batched.rounds_remaining_hint`; HK-PR lanes know
        their remaining Taylor levels exactly
        (:func:`repro.core.batched.hk_rounds_remaining`).  Free of device
        syncs: consumes the host mirror of the tick's coalesced status
        readback.  For a pool that has never pulled status, every occupied
        lane is freshly injected (t = 0, singleton frontier), for which the
        survival hint is exactly 1 round — synthesized host-side."""
        mask = np.array([l is not None for l in self.lane])
        sh = self._status_host
        if sh is None:
            return np.where(mask, 1, 0)
        iters, fc = sh[STATUS_ITER], sh[STATUS_FRONTIER]
        if self.method == "pr_nibble":
            hints = rounds_remaining_hint(iters, fc)
        else:
            N, _ = self.statics
            hints = hk_rounds_remaining(
                iters, sh[STATUS_FINISHED].astype(bool), fc, N)
        return np.where(mask, hints, 0)

    def pending_ticks(self) -> int:
        """Estimated scheduler ticks until this pool drains: the slowest
        active lane's rounds / rounds_per_step, plus one such stretch per
        refill wave the queue implies.  Crude by design — the scheduler
        multiplies it by the tick-cost EMA to rank pools, nothing else."""
        if not self.has_work():
            return 0
        r = max(self.engine.rounds_per_step, 1)
        hints = self.pending_rounds()
        lane_part = int(math.ceil(int(hints.max()) / r)) if hints.size else 0
        waves = math.ceil(len(self.queue) / max(len(self.lane), 1))
        return max(lane_part + waves * max(lane_part, 1), 1)

    def refill(self) -> None:
        for i in range(len(self.lane)):
            if self.lane[i] is not None or not self.queue:
                continue
            idx, req = self.queue.popleft()
            self.lane[i] = (idx, req)
            self.eps[i] = req.eps
            self.alpha[i] = req.alpha
            # host scalars: a jnp conversion would compile after warmup
            lane, seed = np.int32(i), np.int32(req.seed)
            if self.backend == "dist":
                self.state = self._dist_inject(self.state, lane, seed)
            else:
                # donated: the old state buffers are consumed in place
                self.state = self.exec.inject(self.state, lane, seed)
            if self._status_host is not None:
                # keep the host status mirror truthful for lanes injected
                # after the last pull: a fresh lane is exactly (unfinished,
                # no overflow, singleton frontier, 0 iters, 0 pushes, no
                # edge work) — so a force-finalize, scheduler hint or the
                # next harvest's edge delta reads correct values without a
                # sync
                self._status_host[:, i] = (0, 0, 1, 0, 0, 0, 0)
            self.engine.stats["injections"] += 1
            rt = self.engine._rt.get(idx)
            if rt is not None:
                rt.phase("resident", lane=i, bucket=self.bucket)
                rt.event("injected", lane=i, seed=req.seed)

    def step(self) -> None:
        active = np.array([l is not None for l in self.lane])
        if not active.any():
            return
        if self.backend == "dist":
            pg = self.engine.handle.partitioned()
            self.state = self._dist_step_for(self.engine.rounds_per_step)(
                pg.indptr, pg.indices, pg.deg, self.state,
                jnp.asarray(self.eps), jnp.asarray(self.alpha),
                jnp.asarray(active))
        else:
            # AOT executable, state donated: no jit-cache lookup, no trace,
            # and the pool buffers update in place
            self.state = self.exec.step(
                self.engine.graph, self.state, jnp.asarray(self.eps),
                jnp.asarray(self.alpha), jnp.asarray(active))
        self.engine.stats["steps"] += 1

    def _region(self, name: str):
        """A tracer region (span + device annotation) when traced."""
        tr = self.engine.tracer
        return contextlib.nullcontext() if tr is None else tr.region(name)

    def _pull_status(self) -> np.ndarray:
        """The tick's ONE device→host sync: the stacked int32[7, B] status
        readback (finished/overflow/frontier/iters/pushes/exchanged/edge
        work), cached on the pool for everything downstream — harvest
        decisions, finalize counters, scheduler hints, trace annotations."""
        st = self.state
        if self.backend == "dist":
            dev = _dist_status(st.front, st.t, st.pushes, st.overflow,
                               st.exchanged, st.edge_work)
        else:
            dev = self.exec.status(st)
        # np.array (not asarray): the mirror must be writable — refill
        # patches freshly injected lanes' rows host-side between pulls
        with self._region("status_wait"):
            self._status_host = np.array(dev)
        self.engine.stats["status_syncs"] += 1
        return self._status_host

    def _ensure_status(self) -> np.ndarray:
        """The host status mirror, pulling it only if this pool has never
        synced (possible for force-finalize before any tick)."""
        if self._status_host is None:
            return self._pull_status()
        return self._status_host

    def _tick_work(self, prev: Optional[np.ndarray],
                   sh: np.ndarray) -> Tuple[np.ndarray, int]:
        """Per-lane edges expanded since the previous status pull, and the
        tick's round count, from two host mirrors (no device access).

        A lane's edges are its ``edge_work`` delta, capped at its rounds ×
        the pool's edge slots per round: a round whose expansion overflowed
        counts its true ``total`` (it reruns a bucket up), and the cap keeps
        the tick's edge-slot use at or under 100 %.  The rounds are the
        largest per-lane iteration delta — the vmapped loop's trip count.
        Before a pool's first pull every lane is fresh or an untouched
        placeholder, so the previous mirror is zeros."""
        if prev is None:
            prev = np.zeros_like(sh)
        rounds = (sh[STATUS_ITER] - prev[STATUS_ITER]).astype(np.int64)
        # edge_work is a running int32: a modular difference survives a wrap
        edges = (sh[STATUS_EDGES].astype(np.uint32)
                 - prev[STATUS_EDGES].astype(np.uint32)).astype(np.int64)
        edges = np.minimum(edges, rounds * self.edge_slots_per_round)
        return edges, int(rounds.max(initial=0))

    def harvest(self) -> Dict[str, int]:
        """Pull the tick's status, account its edge work, annotate traced
        requests, and finalize or promote every finished lane.  Returns the
        tick's work counters (``edges``, ``edge_slots``, ``rounds``,
        ``lanes_active``; zeros when no lane was active)."""
        active = [slot is not None for slot in self.lane]
        if not any(active):
            return dict(edges=0, edge_slots=0, rounds=0, lanes_active=0)
        prev = self._status_host
        sh = self._pull_status()
        edges, rounds = self._tick_work(prev, sh)
        work = dict(edges=int(edges.sum()),
                    edge_slots=len(self.lane) * self.edge_slots_per_round
                    * rounds,
                    rounds=rounds, lanes_active=sum(active))
        stats = self.engine.stats
        stats["edges_touched"] += work["edges"]
        stats["edge_slots"] += work["edge_slots"]
        finished = sh[STATUS_FINISHED].astype(bool)
        ovf = sh[STATUS_OVERFLOW].astype(bool)
        count = sh[STATUS_FRONTIER]
        # Per-lane request annotations (traced runs only): every observable
        # rides the coalesced readback — tracing costs no extra sync.
        if self.engine.tracer is not None:
            for i, slot in enumerate(self.lane):
                if slot is None:
                    continue
                rt = self.engine._rt.get(slot[0])
                if rt is not None:
                    obs = dict(frontier=int(count[i]),
                               pushes=int(sh[STATUS_PUSHES][i]),
                               edges=int(edges[i]),
                               overflow=bool(ovf[i]),
                               finished=bool(finished[i]))
                    if self.backend == "dist":
                        obs["exchanged"] = int(sh[STATUS_EXCHANGED][i])
                    rt.event("lane_obs", **obs)
        for i, slot in enumerate(self.lane):
            if slot is None or not finished[i]:
                continue
            idx, req = slot
            self.lane[i] = None
            rt = self.engine._rt.get(idx)
            if ovf[i] and self.engine._promote(idx, req, self.bucket):
                if rt is not None:
                    rt.event("promoted", from_bucket=self.bucket,
                             to_bucket=self.bucket + 1)
                continue
            if rt is not None:
                rt.event("harvest", frontier=int(count[i]),
                         overflow=bool(ovf[i]))
                rt.phase("sweep", bucket=self.bucket)
            self.engine._complete(idx, self._finalize(i, req, bool(ovf[i])))
        return work

    def force_finalize(self, i: int) -> ClusterResult:
        """Harvest lane ``i`` *now*, finished or not: sweep whatever
        diffusion mass the lane has accumulated so far and free the slot.
        The deadline scheduler uses this to turn an expired request into a
        best-effort partial result instead of letting it finish late."""
        idx, req = self.lane[i]
        self.lane[i] = None
        ovf = bool(self._ensure_status()[STATUS_OVERFLOW][i])
        rt = self.engine._rt.get(idx)
        if rt is not None:
            rt.event("expired", lane=i, bucket=self.bucket)
            rt.phase("sweep", bucket=self.bucket, partial=True)
        return self._finalize(i, req, ovf)

    def _finalize(self, i: int, req: ClusterRequest,
                  overflowed: bool) -> ClusterResult:
        eng = self.engine
        n = eng.graph.n
        cap_n, cap_se = self.cap_n, self.sweep_cap_e
        max_cap_se = eng.sweep_cap_e << eng.max_bucket
        sh = self._ensure_status()
        size = None
        if self.exec is not None:
            # Harvest-gather executable: slice the one finished lane's
            # support out of the pool and sweep it on-device — only the
            # order buffer, 4 counters, and φ cross to the host, never the
            # pool state.
            with self._region("sweep_dispatch"):
                order, meta, phi = self.exec.sweep(eng.graph, self.state,
                                                   np.int32(i))
                meta = np.asarray(meta)   # [best_size, best_volume, nnz, ovf]
                phi = np.asarray(phi)
                order = np.asarray(order)
            sweep_ovf = bool(meta[3])
            exhausted = (cap_se >= max_cap_se
                         and (self.backend == "sparse" or cap_n >= n))
            if not sweep_ovf or exhausted:
                size = int(meta[0])
                conductance = float(phi)
                volume, support = int(meta[1]), int(meta[2])
                members = order[:size].astype(np.int32)
                overflowed = overflowed or sweep_ovf
        if size is None:
            # Sweep workspace too small at pool caps (rare), or a dist lane
            # (no local sweep executable): sweep through the jit path on
            # the capacity ladder — the diffusion state is still resident,
            # so this costs a sweep, never a re-run, and each shape
            # compiles once.
            if self.exec is not None:   # pool caps already tried above
                cap_n = min(cap_n * 2, n)
                cap_se = min(cap_se * 2, max_cap_se)
            if self.backend == "sparse":
                # sparse lanes sweep their own support — the grid is cap_v,
                # so only the sweep edge workspace can need a retry
                p_sv = jax.tree.map(lambda buf: buf[i], self.state.p)
                while True:
                    sw = sweep_cut_sparse(eng.graph, p_sv.ids, p_sv.vals,
                                          p_sv.count, cap_se,
                                          backend=self.ops_backend)
                    if not bool(sw.overflow) or cap_se >= max_cap_se:
                        break
                    cap_se = min(cap_se * 2, max_cap_se)
            else:
                # dist lanes sweep on the handle's local CSR: the sharded p
                # row is sliced back to the true vertex count (sentinel
                # padding can never enter the sweep), and — the rows being
                # bit-identical to a dense lane's — the sweep result is too
                p_i = (self.state.p[i][: n] if self.backend == "dist"
                       else self.state.p[i])
                while True:
                    sw = sweep_cut_dense(eng.graph, p_i, cap_n, cap_se,
                                         self.ops_backend)
                    if not bool(sw.overflow) or (cap_n >= n and
                                                 cap_se >= max_cap_se):
                        break
                    cap_n = min(cap_n * 2, n)
                    cap_se = min(cap_se * 2, max_cap_se)
            overflowed = overflowed or bool(sw.overflow)
            size = int(sw.best_size)
            conductance = float(sw.best_conductance)
            volume, support = int(sw.best_volume), int(sw.nnz)
            members = np.asarray(sw.order)[:size].astype(np.int32)
        return ClusterResult(
            request=req,
            conductance=conductance,
            size=size,
            volume=volume,
            support=support,
            cluster=members,
            pushes=int(sh[STATUS_PUSHES][i]),
            iterations=int(sh[STATUS_ITER][i]),
            bucket=self.bucket,
            overflow=overflowed,
            backend=self.backend,
            ops_backend=self.ops_backend,
        )


# -------------------------------------------------------------------- engine

class LocalClusterEngine:
    """Continuous-batching server for local clustering queries on one graph.

    >>> eng = LocalClusterEngine(graph, batch_slots=8)
    >>> results = eng.run([ClusterRequest(seed=s) for s in seeds])

    ``run`` preserves request order.  ``submit``/``poll``/``drain`` expose the
    incremental interface for callers interleaving their own work.
    """

    def __init__(self, graph, batch_slots: int = 8,
                 cap_f: int = 1 << 12, cap_e: int = 1 << 16,
                 cap_n: int = 1 << 11, sweep_cap_e: int = 1 << 17,
                 max_cap_e: int = 1 << 26, rounds_per_step: int = 16,
                 lru_pools: int = 4, cap_v: int = 1 << 12,
                 backend: str = "auto", sparse_ratio: int = 4,
                 ops_backend: str = "auto", cap_x: int = 1 << 12,
                 dist_chip_budget: Optional[int] = None,
                 tracer: Optional[Tracer] = None,
                 cost_ema_alpha: float = 0.3,
                 result_cache=1024):
        """``graph`` is any graph-like — a resident ``CSRGraph`` or a
        :class:`~repro.graphs.handle.GraphHandle` (possibly sharded over a
        mesh, which unlocks the ``dist`` lane pools).

        ``backend`` is the engine-wide default lane type: "dense", "sparse",
        "dist" (sharded handles only), or "auto" (pick per request by
        :func:`repro.core.batched_sparse.pick_backend` with ``sparse_ratio``
        and — when the handle is sharded — the fits-on-chip rule against
        ``dist_chip_budget`` bytes of dense per-lane state).
        ``cap_v`` is the sparse lanes' value capacity K at bucket 0;
        ``cap_x`` is the dist lanes' per-owner exchange-bucket capacity at
        bucket 0.  ``ops_backend`` is the engine-wide default *kernel*
        backend ("xla" | "pallas" | "auto" → "xla") — orthogonal to the
        lane type; requests may pin their own via
        ``ClusterRequest.ops_backend``.  Results are bit-identical across
        lane backends for the dense/dist pair (guarantee #7) and across
        kernel backends where XLA folds in update order (guarantee #6).

        ``tracer`` (a :class:`repro.serve.tracing.Tracer`, default None =
        tracing off) records a span tree per request and per-tick pool
        spans; tracing only *observes* state the engine computed, so traced
        results are bit-identical to untraced ones (docs/algorithms.md,
        guarantee #8).  ``cost_ema_alpha`` is the smoothing factor of every
        pool's tick-cost EMA (the scheduler's cost model).

        ``result_cache`` is the versioned seed→result LRU
        (:mod:`repro.serve.result_cache`): an int is its entry capacity, a
        :class:`~repro.serve.result_cache.ResultCache` instance is shared
        as-is (several engines over one graph may pool their hits), and
        ``0``/``None`` disables caching.  A hit resolves at :meth:`submit`
        — no lane, no tick — and is bit-identical to recomputing
        (guarantee #9); bumping the handle's graph version invalidates
        every entry at once."""
        if backend not in ("auto", "dense", "sparse", "dist"):
            raise ValueError(f"unknown backend: {backend!r}")
        self.handle = as_handle(graph)
        if backend == "dist":
            if not self.handle.is_sharded:
                raise ValueError(
                    "backend='dist' needs a sharded GraphHandle "
                    "(GraphHandle.shard(csr, mesh))")
            self.handle.require_mesh()   # fail at construction, not submit
        self.ops_backend = core_ops.resolve(ops_backend)
        self.batch_slots = batch_slots
        self.cap_f = cap_f
        self.cap_e = cap_e
        self.cap_n = cap_n
        self.sweep_cap_e = sweep_cap_e
        self.cap_v = cap_v
        self.cap_x = cap_x
        self.backend = backend
        self.sparse_ratio = sparse_ratio
        self.dist_chip_budget = dist_chip_budget
        self.rounds_per_step = rounds_per_step
        self.lru_pools = lru_pools
        self.max_bucket = max(0, (max_cap_e // cap_e).bit_length() - 1)
        self.pools: "OrderedDict[tuple, _Pool]" = OrderedDict()
        # AOT executable cache: pool key → compiled tick programs.  Outlives
        # pool eviction by design — see serve/aot.py.
        self._exec_cache = ExecutableCache()
        if isinstance(result_cache, ResultCache):
            self.result_cache: Optional[ResultCache] = result_cache
        elif result_cache:
            self.result_cache = ResultCache(int(result_cache))
        else:
            self.result_cache = None
        # edges_touched / edge_slots: Σ edges the ticks' rounds expanded,
        # and Σ edge slots they computed (B × cap_e × rounds a tick) — their
        # ratio is the diffusion's edge-slot use.  backend_compiles: XLA
        # backend compiles in this process while the engine lives.
        self.stats: Dict = dict(steps=0, injections=0, promotions=0,
                                completed=0, pools_created=0,
                                pools_evicted=0, partial_harvests=0,
                                status_syncs=0, aot_compiles=0,
                                aot_cache_hits=0, aot_compile_s=0.0,
                                result_cache_hits=0, result_cache_misses=0,
                                edges_touched=0, edge_slots=0,
                                backend_compiles=0, bucket_shapes=set())
        self._results: Dict[int, ClusterResult] = {}
        self._next_idx = 0
        self.tracer = tracer
        self.cost_ema_alpha = cost_ema_alpha
        # ticket → RequestTrace for in-flight traced requests; traces are
        # finished and dropped at result pickup
        self._rt: Dict[int, RequestTrace] = {}
        self._compile_lock = threading.Lock()
        watch_compiles(self)

    def on_compile(self, fun_name: str, seconds: float) -> None:
        """Count one XLA backend compile of this process (called on the
        compiling thread) and, when traced, record it as a ``compile``
        event under the active scope."""
        with self._compile_lock:
            self.stats["backend_compiles"] += 1
        tr = self.tracer
        if tr is not None:
            tr.compile_event(fun_name, seconds)

    @property
    def graph(self) -> CSRGraph:
        """The resident-CSR view (materialized from the partition slabs and
        cached when the engine was built sharded-first): what the local lane
        pools step against and every harvest sweeps with."""
        return self.handle.local()

    # -- AOT compile lifecycle ----------------------------------------------

    def _pool_caps(self, key: tuple) -> Dict[str, int]:
        """Workspace capacities of the pool at ``key``'s bucket — the
        doubling ladder of the single-seed drivers, clamped at the graph's
        natural sizes (and, for dist pools, at the shard's row count /
        the edge workspace).  Centralized so the pool construction and the
        AOT kernel builder can never disagree on a shape."""
        _method, backend, _statics, _ops, bucket, _topo = key
        n = self.handle.n
        caps = dict(cap_f=min(self.cap_f << bucket, n + 1),
                    cap_e=self.cap_e << bucket,
                    cap_n=min(self.cap_n << bucket, n),
                    sweep_cap_e=self.sweep_cap_e << bucket,
                    cap_v=min(self.cap_v << bucket, n + 1))
        if backend == "dist":
            pg = self.handle.partitioned()
            # dist cap_f is *per shard*: a local frontier can never exceed
            # the shard's row count
            caps["cap_f"] = min(self.cap_f << bucket, pg.rows_per + 1)
            caps["cap_x"] = min(self.cap_x << bucket, caps["cap_e"])
        return caps

    def _executables_for(self, key: tuple):
        """The AOT-compiled tick executables for pool ``key``, building
        (lower + compile against the pool's exact avals) at most once per
        key for the engine's lifetime.  Ladder promotion hops between
        already-compiled buckets; an LRU-evicted pool's re-creation is a
        cache hit, never a re-trace."""
        method, backend, statics, ops_backend, _bucket, _topo = key
        caps = self._pool_caps(key)
        n = self.handle.n

        def build(name):
            if backend == "sparse":
                kern = sparse_lane_kernels(
                    n, statics, caps["cap_f"], caps["cap_v"], caps["cap_e"],
                    caps["sweep_cap_e"], self.rounds_per_step, ops_backend)
            else:
                kern = dense_lane_kernels(
                    n, method, statics, caps["cap_f"], caps["cap_e"],
                    caps["cap_n"], caps["sweep_cap_e"],
                    self.rounds_per_step, ops_backend)
            return compile_lane_executables(kern, self.graph,
                                            self.batch_slots, name)

        ex = self._exec_cache.get(key, build)
        cs = self._exec_cache.stats()
        self.stats["aot_compiles"] = cs["compiles"]
        self.stats["aot_cache_hits"] = cs["hits"]
        self.stats["aot_compile_s"] = cs["compile_seconds"]
        return ex

    def warmup(self, requests: Optional[List[ClusterRequest]] = None,
               max_bucket: int = 1) -> Dict:
        """Eagerly AOT-compile the tick executables every request in
        ``requests`` would touch, over buckets ``0..max_bucket`` of the
        capacity ladder — so the serving steady state never pays a
        first-touch trace.  ``requests`` are *prototypes* (seed/α/ε don't
        matter — only the pool-key material: method, statics, resolved
        backends); default is one plain PR-Nibble prototype.  Dist pools
        keep the jit path (their shard_map programs warm on first tick) and
        are skipped.  Returns ``dict(seconds, compiled, buckets)``."""
        t0 = time.perf_counter()
        if requests is None:
            requests = [ClusterRequest(seed=0)]
        before = self._exec_cache.stats()["compiles"]
        hi = min(max_bucket, self.max_bucket)
        for req in requests:
            for b in range(hi + 1):
                key = self._pool_key(req, b)
                if key[1] == "dist":
                    continue
                self._executables_for(key)
        return dict(seconds=time.perf_counter() - t0,
                    compiled=self._exec_cache.stats()["compiles"] - before,
                    buckets=hi + 1)

    # -- result cache --------------------------------------------------------

    def cached_result(self, req: ClusterRequest) -> Optional[ClusterResult]:
        """The cached converged result for ``req`` at the current graph
        version, or None.  A hit is a fresh :class:`ClusterResult` copy
        carrying ``req`` itself — bit-identical cluster/φ to what a lane
        would compute (guarantee #9)."""
        if self.result_cache is None:
            return None
        key = result_key(req, self._resolve_backend(req),
                         self._resolve_ops_backend(req), self.handle.version)
        res = self.result_cache.get(key, request=req)
        self.stats["result_cache_hits"] = self.result_cache.hits
        self.stats["result_cache_misses"] = self.result_cache.misses
        return res

    # -- scheduling ----------------------------------------------------------

    def _resolve_backend(self, req: ClusterRequest) -> str:
        """Which lane type serves ``req``: its pin, else the engine default,
        with "auto" resolved by the graph-size/K (and, for sharded handles,
        fits-on-chip) heuristic.  Sparse and dist state exists only for plain
        PR-Nibble (β = 1): a *request-level* sparse/dist pin on an
        unsupported query is an error; an engine-level "sparse"/"dist"
        default or an "auto" resolution falls back to dense for those
        queries."""
        b = req.backend if req.backend is not None else self.backend
        if b not in ("auto", "dense", "sparse", "dist"):
            raise ValueError(f"unknown backend: {b!r}")
        if b == "dist":
            if not self.handle.is_sharded:
                raise ValueError("backend='dist' needs a sharded GraphHandle")
            # a sharded handle without a mesh can't run dist pools — raise
            # here (submit validates on the caller's thread) rather than
            # from _Pool.__init__ inside the scheduler's drive thread
            self.handle.require_mesh()
        lane_ok = req.method == "pr_nibble" and req.beta == 1.0
        if not lane_ok:
            if req.backend in ("sparse", "dist"):
                raise ValueError(
                    f"backend={req.backend!r} supports only pr_nibble with "
                    f"beta=1.0 (got method={req.method!r}, beta={req.beta})")
            return "dense"
        if b == "auto":
            # dist is only reachable for auto resolution when the handle can
            # actually run it (sharded AND carries a mesh) — a mesh-less
            # sharded handle falls back to the local heuristic instead of
            # exploding at submit time
            dist_ready = self.handle.is_sharded and self.handle.mesh is not None
            b = pick_backend(
                self.handle.n, self.cap_v, self.sparse_ratio,
                num_shards=self.handle.num_shards if dist_ready else 1,
                chip_budget=self.dist_chip_budget)
        return b

    def _resolve_ops_backend(self, req: ClusterRequest) -> str:
        """Kernel backend serving ``req``: its pin, else the engine default
        ("auto" resolved at engine construction)."""
        if req.ops_backend is None:
            return self.ops_backend
        return core_ops.resolve(req.ops_backend)

    def _pool_key(self, req: ClusterRequest, bucket: int) -> tuple:
        """(method, backend, statics, ops_backend, bucket, topo) — ``topo``
        is the shard topology (axis, D) for dist pools, None otherwise, so
        dist pools can never alias local pools (or each other across
        meshes) in the compile cache, the LRU, or the telemetry labels.
        A request bound for a sparse lane must have ε > 0
        (:func:`~repro.core.pr_nibble_sparse.check_sparse_eps`)."""
        if req.method == "pr_nibble":
            statics = (req.optimized, req.beta)
        elif req.method == "hk_pr":
            statics = (req.N, req.t)
        else:
            raise ValueError(f"unknown method: {req.method!r}")
        backend = self._resolve_backend(req)
        if backend == "sparse":
            check_sparse_eps(req.eps)
        topo = ((self.handle.axis, self.handle.num_shards)
                if backend == "dist" else None)
        return (req.method, backend, statics,
                self._resolve_ops_backend(req), bucket, topo)

    def _enqueue(self, idx: int, req: ClusterRequest, bucket: int) -> None:
        key = self._pool_key(req, bucket)
        pool = self.pools.get(key)
        if pool is None:
            pool = _Pool(self, key)
            self.pools[key] = pool
        self.pools.move_to_end(key)
        pool.queue.append((idx, req))   # before evict: a pool with work is safe
        rt = self._rt.get(idx)
        if rt is not None:
            rt.phase("pool_queue", pool=pool_label(key), bucket=bucket)
        self._evict_idle()

    def _promote(self, idx: int, req: ClusterRequest, bucket: int) -> bool:
        """Re-enqueue an overflowed request one bucket up.  Returns False if
        the capacity ladder is exhausted (caller reports overflow)."""
        if bucket + 1 > self.max_bucket:
            return False
        self.stats["promotions"] += 1
        self._enqueue(idx, req, bucket + 1)
        return True

    def _complete(self, idx: int, res: ClusterResult) -> None:
        self._results[idx] = res
        self.stats["completed"] += 1
        if self.result_cache is not None and not res.deadline_missed:
            self.result_cache.put(
                result_key(res.request, res.backend, res.ops_backend,
                           self.handle.version),
                res)
        rt = self._rt.get(idx)
        if rt is not None:
            # inf conductance (empty partial harvest) is not valid JSON
            phi = res.conductance if math.isfinite(res.conductance) else None
            rt.phase("deliver", conductance=phi, size=res.size,
                     pushes=res.pushes)

    def _evict_idle(self) -> None:
        while len(self.pools) > self.lru_pools:
            victim = next((k for k, p in self.pools.items()
                           if not p.has_work()), None)
            if victim is None:
                break
            del self.pools[victim]
            self.stats["pools_evicted"] += 1

    # -- public API ----------------------------------------------------------

    def submit(self, req: ClusterRequest,
               _trace: Optional[RequestTrace] = None,
               _skip_cache: bool = False) -> int:
        """Queue a request; returns a ticket usable with :meth:`result`.

        A result-cache hit short-circuits the queue entirely: the ticket is
        issued already-resolved (ready for :meth:`result` immediately), no
        lane is occupied, no tick runs.  ``_skip_cache`` lets the async
        layer opt out when it has already consulted the cache itself.

        ``_trace`` lets the async layer hand down the request's
        :class:`~repro.serve.tracing.RequestTrace` (already carrying its
        scheduler-side ``queued`` phase); without one, a traced engine opens
        a fresh trace at submission."""
        self._pool_key(req, 0)  # validate method early
        idx = self._next_idx
        self._next_idx += 1
        rt = _trace
        if rt is None and self.tracer is not None:
            rt = self.tracer.request(seed=req.seed, method=req.method)
        if rt is not None:
            self._rt[idx] = rt
        if not _skip_cache:
            hit = self.cached_result(req)
            if hit is not None:
                if rt is not None:
                    rt.event("cache_hit", seed=req.seed)
                self._complete(idx, hit)
                return idx
        self._enqueue(idx, req, 0)
        return idx

    def live_pools(self) -> List[Tuple[tuple, _Pool]]:
        """Snapshot of (key, pool) pairs that currently have work, in LRU
        order (least recently progressed/enqueued first).  The deadline
        scheduler plans over this; :meth:`poll` sweeps it."""
        return [(k, p) for k, p in list(self.pools.items()) if p.has_work()]

    def tick_pool(self, key: tuple) -> Optional[float]:
        """One refill → step → harvest pass of a *single* pool — the unit of
        work the deadline scheduler orders.  Returns the measured wall time
        in seconds (also folded into the pool's ``cost_ema``), or None if
        the pool is gone or idle.  A progressed pool is moved to the MRU end
        so LRU iteration (:meth:`poll`) stays fair."""
        pool = self.pools.get(key)
        if pool is None or not pool.has_work():
            return None
        tr = self.tracer
        if tr is None:
            t0 = time.perf_counter()
            pool.refill()
            pool.step()
            pool.harvest()  # device→host sync: the measured time is honest
            dt = time.perf_counter() - t0
        else:
            # the tick span ends with the tick's work counters: edges,
            # edge_slots, rounds, lanes_active
            label = pool_label(key)
            tick_sid = tr.begin("tick", cat="pool", pool=label,
                                occupancy=pool.occupancy(),
                                queued=len(pool.queue),
                                cost_ema=pool.cost_ema)
            work = {}
            try:
                with tr.scope(parent=tick_sid), \
                        tr.device_span(f"tick:{label}"):
                    t0 = time.perf_counter()
                    with tr.region("refill"):
                        pool.refill()
                    with tr.region("step"):
                        pool.step()
                    with tr.span("harvest", cat="pool", parent=tick_sid):
                        work = pool.harvest()
                    dt = time.perf_counter() - t0
            finally:
                tr.end(tick_sid, **work)
        pool.note_tick(dt)
        if key in self.pools:   # harvest may promote+evict this very pool
            self.pools.move_to_end(key)
        return dt

    def poll(self) -> bool:
        """One scheduler sweep: refill, step, and harvest every live pool,
        visiting pools in LRU order and moving each progressed pool to the
        MRU end.  A continuously-refilled hot pool therefore sinks behind
        colder pools between sweeps and can never starve their harvest under
        ``submit()``/``poll()`` interleaving.  Returns True if any pool made
        progress."""
        progressed = False
        for key in list(self.pools):  # LRU order: coldest pools first
            if self.tick_pool(key) is not None:
                progressed = True
        return progressed

    def pending(self) -> int:
        return sum(1 for p in self.pools.values() if p.has_work())

    def drain(self) -> None:
        """Run the scheduler until every submitted request has a result."""
        while self.poll():
            pass
        self._evict_idle()

    def _ticket_status(self, ticket) -> str:
        """"ready" | "pending" | "never-issued" | "consumed"."""
        if ticket in self._results:
            return "ready"
        if (not isinstance(ticket, (int, np.integer)) or ticket < 0
                or ticket >= self._next_idx):
            return "never-issued"
        for pool in self.pools.values():
            if ticket in pool.tickets():
                return "pending"
        return "consumed"

    def result(self, ticket: int) -> ClusterResult:
        """Pop the finished :class:`ClusterResult` for ``ticket``.  Raises
        :class:`UnknownTicket` (a ``KeyError``) with a diagnosis — never
        issued, already consumed, or still in flight — instead of a bare
        ``dict.pop`` KeyError."""
        status = self._ticket_status(ticket)
        if status == "ready":
            res = self._results.pop(ticket)
            self._finish_trace(ticket, res)
            return res
        if status == "pending":
            raise UnknownTicket(
                f"ticket {ticket} is still in flight — call poll()/drain() "
                f"until it completes, or peek() to test readiness")
        if status == "never-issued":
            raise UnknownTicket(
                f"ticket {ticket!r} was never issued by this engine")
        raise UnknownTicket(
            f"ticket {ticket} was already consumed "
            f"(result() returns each result exactly once)")

    def peek(self, ticket: int) -> Optional[ClusterResult]:
        """Non-consuming :meth:`result`: the finished result, or None while
        the ticket is still in flight.  Raises :class:`UnknownTicket` for
        never-issued or already-consumed tickets."""
        status = self._ticket_status(ticket)
        if status == "ready":
            return self._results[ticket]
        if status == "pending":
            return None
        raise UnknownTicket(
            f"ticket {ticket!r} was "
            + ("never issued by this engine" if status == "never-issued"
               else "already consumed"))

    def take_completed(self, tickets=None) -> Dict[int, ClusterResult]:
        """Pop finished results in bulk: {ticket: result} (exactly-once,
        like :meth:`result`).  ``tickets`` restricts the pickup to that set
        — the deadline scheduler passes the tickets it owns, so results
        submitted to a shared engine out-of-band stay claimable via
        :meth:`result`.  ``None`` pops everything."""
        if tickets is None:
            out, self._results = self._results, {}
        else:
            tickets = set(tickets)
            out = {t: r for t, r in self._results.items() if t in tickets}
            for t in out:
                del self._results[t]
        for t, r in out.items():
            self._finish_trace(t, r)
        return out

    def _finish_trace(self, ticket: int, res: ClusterResult) -> None:
        """Close a picked-up request's trace (the ``deliver`` phase ends at
        pickup, which is what the request's consumer actually waited for)."""
        rt = self._rt.pop(ticket, None)
        if rt is not None:
            rt.finish("expired" if res.deadline_missed else "resolved")

    def trace_for(self, ticket: int) -> Optional[RequestTrace]:
        """The in-flight :class:`~repro.serve.tracing.RequestTrace` for
        ``ticket`` (None once picked up, or for untraced requests)."""
        return self._rt.get(ticket)

    def harvest_partial(self, ticket: int) -> bool:
        """Force-finish a live request *now* for deadline expiry: a request
        resident in a lane is swept as-is (best-effort cluster from the
        partial diffusion); a still-queued request completes empty.  The
        result is recorded with ``deadline_missed=True`` and retrieved via
        :meth:`result`/:meth:`take_completed` like any other.  Returns False
        when the ticket isn't live (unknown, finished, or consumed)."""
        for key, pool in list(self.pools.items()):
            for i, slot in enumerate(pool.lane):
                if slot is not None and slot[0] == ticket:
                    res = pool.force_finalize(i)
                    res.deadline_missed = True
                    self.stats["partial_harvests"] += 1
                    self._complete(ticket, res)
                    return True
            for entry in pool.queue:
                if entry[0] == ticket:
                    pool.queue.remove(entry)
                    _, req = entry
                    rt = self._rt.get(ticket)
                    if rt is not None:
                        rt.event("expired", queued=True,
                                 pool=pool_label(key))
                    res = ClusterResult(
                        request=req, conductance=float("inf"), size=0,
                        volume=0, support=0,
                        cluster=np.zeros(0, np.int32), pushes=0,
                        iterations=0, bucket=pool.bucket, overflow=False,
                        backend=pool.backend, ops_backend=pool.ops_backend,
                        deadline_missed=True)
                    self.stats["partial_harvests"] += 1
                    self._complete(ticket, res)
                    return True
        return False

    def run(self, requests: List[ClusterRequest]) -> List[ClusterResult]:
        """Submit, drain, and return results in request order."""
        tickets = [self.submit(r) for r in requests]
        self.drain()
        return [self.result(t) for t in tickets]

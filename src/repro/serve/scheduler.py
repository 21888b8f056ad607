"""Deadline-aware asynchronous serving on top of ``LocalClusterEngine``.

Local clustering does work proportional to the *cluster*, not the graph —
which makes per-query latency wildly heterogeneous: one request drains in a
couple of push rounds while its neighbor runs thousands.  A drain-everything
loop (``LocalClusterEngine.run``) is the wrong shape for that regime; this
module adds the scheduler brain:

  * **Futures-based submission** — ``submit(req, deadline_ms=…, priority=…)``
    returns a :class:`ClusterFuture` (``done()/result(timeout)/
    add_done_callback()``) immediately; callers interleave their own work.
  * **EDF tick planner** — each scheduler tick orders pool stepping by
    *slack*: the earliest resident deadline minus now minus the pool's
    estimated time-to-drain.  The cost model is measured, not guessed:
    per-pool EMA of tick wall time (fed to and read back from the
    :class:`~repro.serve.telemetry.MetricsRegistry`) × the pool's
    pending-ticks estimate (rounds-remaining hints from
    ``repro.core.batched`` / ``repro.core.batched_sparse``).
  * **Deadline expiry** — an overdue request is *harvested*, not abandoned:
    a resident lane is swept as-is into a best-effort partial result, a
    still-queued request completes empty; either way the future resolves
    with ``result.deadline_missed=True`` instead of silently finishing late.
    A request that completes naturally but after its deadline is delivered
    in full, also flagged.
  * **Admission control** — at most ``max_queue`` requests in flight;
    ``submit`` raises :class:`QueueFull` beyond that (backpressure, never
    unbounded buffering).
  * **Drive modes** — ``serve_forever()`` starts a daemon thread running
    the tick loop; or call :meth:`AsyncClusterEngine.tick` yourself for
    deterministic single-threaded driving (what the tests do).

Scheduling never changes answers (docs/algorithms.md, guarantee #3): the
planner only chooses *when* each pool's lanes step, and every lane steps the
same round function through the same trajectory regardless of interleaving.
A stream served with no deadlines is bit-identical, per request, to
``LocalClusterEngine.run()`` on the same requests.

Threading contract: ``submit``/``ClusterFuture`` are thread-safe; the engine
itself is single-threaded and is only ever touched under ``_engine_lock``
(by the drive thread, or by whoever calls ``tick()``).  Callbacks run on the
resolving (drive) thread — keep them short.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

from repro.graphs.csr import CSRGraph
from repro.graphs.handle import GraphHandle
from repro.graphs.partition import PartitionedCSR
from .cluster_engine import (ClusterRequest, ClusterResult,
                             LocalClusterEngine)
from .telemetry import MetricsRegistry, load_cost_table, lookup_cost, \
    pool_label
from .tracing import RequestTrace, Tracer

__all__ = ["AsyncClusterEngine", "ClusterFuture", "QueueFull"]


class QueueFull(RuntimeError):
    """Admission control: the scheduler already holds ``max_queue`` unresolved
    requests.  Back off and resubmit — the bound is backpressure, not an
    error in the request."""


class ClusterFuture:
    """Handle for one submitted request; resolves to a :class:`ClusterResult`.

    The deliberately-small subset of ``concurrent.futures.Future`` the
    serving workload needs: ``done()``, blocking ``result(timeout)``, and
    ``add_done_callback(fn)`` (called with the future, on the resolving
    thread; immediately if already resolved).  ``latency_ms`` is the
    submit→resolve wall time once done.
    """

    def __init__(self, request: ClusterRequest) -> None:
        self.request = request
        self.ticket: Optional[int] = None     # engine ticket, set at admission
        self.trace: Optional[RequestTrace] = None  # set when tracing is on
        self.submitted = time.monotonic()     # deadline/latency anchor
        self.latency_ms: Optional[float] = None
        self._cond = threading.Condition()
        self._result: Optional[ClusterResult] = None
        self._done = False
        self._callbacks: List[Callable[["ClusterFuture"], None]] = []

    def done(self) -> bool:
        with self._cond:
            return self._done

    def result(self, timeout: Optional[float] = None) -> ClusterResult:
        """Block until resolved (or ``timeout`` seconds → ``TimeoutError``)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout=timeout):
                raise TimeoutError(
                    f"request (seed={self.request.seed}) not done "
                    f"after {timeout}s")
            return self._result

    def add_done_callback(self,
                          fn: Callable[["ClusterFuture"], None]) -> None:
        with self._cond:
            if not self._done:
                self._callbacks.append(fn)
                return
        fn(self)

    def _resolve(self, result: ClusterResult, latency_ms: float) -> None:
        with self._cond:
            self._result = result
            self.latency_ms = latency_ms
            self._done = True
            callbacks, self._callbacks = self._callbacks, []
            self._cond.notify_all()
        for fn in callbacks:
            try:
                fn(self)
            except Exception:       # callbacks must not kill the drive loop
                import traceback
                traceback.print_exc()


@dataclasses.dataclass
class _Inflight:
    """Scheduler-side record of one admitted request."""
    future: ClusterFuture
    submitted: float                 # monotonic submit time
    deadline: Optional[float]        # absolute monotonic deadline (or None)
    priority: int


class AsyncClusterEngine:
    """Deadline-aware async front end over one :class:`LocalClusterEngine`.

    >>> sched = AsyncClusterEngine(graph, batch_slots=8, max_queue=64)
    >>> sched.serve_forever()
    >>> fut = sched.submit(ClusterRequest(seed=7), deadline_ms=50.0)
    >>> fut.add_done_callback(lambda f: print(f.result().conductance))
    >>> sched.shutdown()

    Parameters
    ----------
    engine_or_graph : an existing ``LocalClusterEngine`` to wrap, or a
        ``CSRGraph`` / ``GraphHandle`` (one is built with
        ``**engine_kwargs``; a sharded handle unlocks the ``dist`` pools,
        scheduled by the same EDF planner through the same tick-cost EMAs).
    max_queue : admission bound on unresolved requests (``QueueFull`` beyond).
    max_pools_per_tick : how many pools one tick steps, in EDF order.  None
        (default) steps every live pool — best throughput; 1 is strict EDF —
        tightest priority, what the EDF tests pin.
    telemetry : a shared :class:`MetricsRegistry`, or None to create one.
    default_deadline_ms : applied to requests that carry no deadline of
        their own (None = best-effort, no deadline).
    tracer : a :class:`~repro.serve.tracing.Tracer` to flight-record every
        request's span tree (installed on the wrapped engine too); None
        (default) inherits the engine's tracer, if any.  On deadline expiry
        the victim's span tree is dumped into ``telemetry`` as a bounded
        postmortem.  Tracing never changes answers (guarantee #8).
    cost_table : characterized tick costs seeding the EDF cost model before
        any EMA exists — a ``serve_bench --characterize`` artifact (path or
        dict; see :func:`~repro.serve.telemetry.load_cost_table`).  Without
        it, a cold pool is costed at ``_DEFAULT_TICK_COST`` until its first
        measured tick, which under-ranks slow pools exactly when deadlines
        are tightest (the first wave).  Measured EMAs always take over.
    """

    _DEFAULT_TICK_COST = 1e-3   # planner's cost guess before a pool's 1st EMA

    def __init__(self, engine_or_graph, *, max_queue: int = 256,
                 max_pools_per_tick: Optional[int] = None,
                 telemetry: Optional[MetricsRegistry] = None,
                 default_deadline_ms: Optional[float] = None,
                 tracer: Optional[Tracer] = None,
                 cost_table=None,
                 **engine_kwargs):
        if isinstance(engine_or_graph, LocalClusterEngine):
            if engine_kwargs:
                raise ValueError("engine_kwargs only apply when constructing "
                                 "the engine from a graph")
            self.engine = engine_or_graph
        elif isinstance(engine_or_graph,
                        (CSRGraph, GraphHandle, PartitionedCSR)):
            # any graph-like the engine itself accepts (as_handle coerces)
            self.engine = LocalClusterEngine(engine_or_graph, **engine_kwargs)
        else:
            raise TypeError(f"expected LocalClusterEngine or a graph-like "
                            f"(CSRGraph | GraphHandle | PartitionedCSR), got "
                            f"{type(engine_or_graph).__name__}")
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self.max_queue = max_queue
        self.max_pools_per_tick = max_pools_per_tick
        self.default_deadline_ms = default_deadline_ms
        self.telemetry = telemetry if telemetry is not None else \
            MetricsRegistry()
        if tracer is not None:
            self.engine.tracer = tracer     # one recorder for both layers
        self.tracer = tracer if tracer is not None else self.engine.tracer
        self.cost_table = load_cost_table(cost_table)
        self.last_plan: List[tuple] = []     # EDF order of the latest tick
        self._mutex = threading.Lock()       # admission queue + records
        self._engine_lock = threading.RLock()  # serializes engine access
        self._admissions: List[ClusterFuture] = []
        self._live: Dict[int, _Inflight] = {}   # ticket → record
        self._inflight = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- submission (any thread) --------------------------------------------

    def submit(self, req: ClusterRequest,
               deadline_ms: Optional[float] = None,
               priority: Optional[int] = None) -> ClusterFuture:
        """Queue a request; returns its :class:`ClusterFuture` immediately.

        ``deadline_ms``/``priority`` override the request's own fields when
        given (the stored request is updated so the result reports the
        effective values).  Raises :class:`QueueFull` when ``max_queue``
        requests are already unresolved.

        A seed→result cache hit resolves the future *here*, on the caller's
        thread: no admission slot consumed, no lane occupied, no tick — the
        engine's cached converged answer (bit-identical to recomputing,
        guarantee #9) comes back before the drive loop ever sees the
        request.  Hits can therefore never be rejected by admission control
        and never miss a deadline.
        """
        updates = {}
        if deadline_ms is not None:
            updates["deadline_ms"] = deadline_ms
        if priority is not None:
            updates["priority"] = priority
        if req.deadline_ms is None and "deadline_ms" not in updates and \
                self.default_deadline_ms is not None:
            updates["deadline_ms"] = self.default_deadline_ms
        if updates:
            req = dataclasses.replace(req, **updates)
        # validate method/backend on the caller's thread, so a malformed
        # request raises here instead of stranding a future in the drive loop
        self.engine._pool_key(req, 0)
        fut = ClusterFuture(req)
        if self.tracer is not None:
            # trace opens on the caller's thread, *before* the future is
            # visible to the drive loop, so the queued phase can never miss
            # the admission that ends it
            fut.trace = self.tracer.request(
                seed=req.seed, method=req.method,
                deadline_ms=req.deadline_ms, priority=req.priority)
            fut.trace.phase("queued")
        # Result-cache probe (the cache and the version read are themselves
        # thread-safe, so no engine lock — a hit must not wait out a tick)
        hit = self.engine.cached_result(req)
        if hit is not None:
            self.telemetry.inc("scheduler/submitted")
            self.telemetry.inc("scheduler/cache_hits")
            latency_ms = (time.monotonic() - fut.submitted) * 1e3
            self.telemetry.observe("scheduler/request_latency",
                                   latency_ms / 1e3)
            self.telemetry.inc("scheduler/completed")
            if fut.trace is not None:
                fut.trace.resolve_cached(seed=req.seed)
            fut._resolve(hit, latency_ms)
            return fut
        with self._mutex:
            if self._inflight >= self.max_queue:
                self.telemetry.inc("scheduler/rejected")
                if fut.trace is not None:
                    fut.trace.finish("rejected")
                raise QueueFull(
                    f"{self._inflight} requests in flight (max_queue="
                    f"{self.max_queue}); back off and resubmit")
            self._inflight += 1
            self._admissions.append(fut)
        self.telemetry.inc("scheduler/submitted")
        self._wake.set()
        return fut

    def inflight(self) -> int:
        """Unresolved requests (admitted + live), the admission-bound gauge."""
        with self._mutex:
            return self._inflight

    # -- the tick (drive thread, or manual caller) --------------------------

    def tick(self) -> bool:
        """One scheduler iteration: admit → plan (EDF) → step pools in plan
        order → resolve completions → expire overdue requests.  Returns True
        if any engine pool progressed.  Safe to call from any thread (fully
        serialized); deterministic when driven single-threaded."""
        with self._engine_lock:
            admitted = self._admit()
            now = time.monotonic()
            plan = self._plan(now)
            self.last_plan = [key for key, _slack in plan]
            budget = (len(plan) if self.max_pools_per_tick is None
                      else self.max_pools_per_tick)
            progressed = False
            for key in self.last_plan[:budget]:
                dt = self.engine.tick_pool(key)
                if dt is None:
                    continue
                progressed = True
                label = pool_label(key)
                self.telemetry.observe(f"pool/{label}/tick_latency", dt)
                self.telemetry.ema(f"pool/{label}/tick_cost").update(dt)
            self._resolve_completed(time.monotonic())
            self._expire(time.monotonic())
            self._resolve_completed(time.monotonic())  # expiry harvests
            self._update_gauges()
            return progressed or admitted > 0

    def drain(self) -> None:
        """Block until every submitted request has resolved.  With the drive
        thread running this just waits; otherwise it ticks inline."""
        while self.inflight() > 0:
            if self._thread is not None and self._thread.is_alive():
                time.sleep(0.001)
            else:
                self.tick()

    # -- background drive mode ----------------------------------------------

    def serve_forever(self, idle_wait: float = 0.005) -> threading.Thread:
        """Start (idempotently) the daemon drive thread: ticks while there is
        work, parks on an event for ``idle_wait`` seconds when idle."""
        with self._mutex:
            if self._thread is not None and self._thread.is_alive():
                return self._thread
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._drive, args=(idle_wait,),
                name="AsyncClusterEngine", daemon=True)
            self._thread.start()
            return self._thread

    def _drive(self, idle_wait: float) -> None:
        while not self._stop.is_set():
            if not self.tick() and self.inflight() == 0:
                self._wake.wait(timeout=idle_wait)
                self._wake.clear()

    def shutdown(self, wait: bool = True) -> None:
        """Stop the drive thread.  ``wait=True`` (default) drains all
        in-flight work first; ``wait=False`` stops promptly and leaves
        unresolved futures pending."""
        if wait:
            self.drain()
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self) -> "AsyncClusterEngine":
        self.serve_forever()
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(wait=exc == (None, None, None))

    # -- internals (all called under _engine_lock) --------------------------

    def _admit(self) -> int:
        with self._mutex:
            batch, self._admissions = self._admissions, []
        for fut in batch:
            ticket = self.engine.submit(fut.request, _trace=fut.trace)
            fut.ticket = ticket
            ddl = fut.request.deadline_ms
            # deadline and latency anchor at the submit() call, not at
            # admission: time spent waiting out a long tick counts
            self._live[ticket] = _Inflight(
                future=fut, submitted=fut.submitted,
                deadline=(None if ddl is None
                          else fut.submitted + ddl / 1000.0),
                priority=fut.request.priority)
        return len(batch)

    def _plan(self, now: float) -> List[tuple]:
        """EDF order over live pools: sort by slack = earliest resident
        deadline − now − estimated cost (tick-cost EMA read back from the
        telemetry registry × pending-ticks).  Pools with no deadlined
        residents sort after all deadlined ones, by descending priority then
        LRU position.  Returns [(pool_key, slack_or_None), …]."""
        entries = []
        for order, (key, pool) in enumerate(self.engine.live_pools()):
            deadlines = []
            priorities = []
            for ticket in pool.tickets():
                rec = self._live.get(ticket)
                if rec is None:
                    continue
                priorities.append(rec.priority)
                if rec.deadline is not None:
                    deadlines.append(rec.deadline)
            # cost estimate: the registry EMA is primary (fed by our ticks);
            # a fresh registry over a warm engine falls back to the pool's
            # own measurement, then to the characterized cost table, and
            # only then to the cold-start default
            ema = self.telemetry.ema_value(
                f"pool/{pool_label(key)}/tick_cost")
            if ema is None:
                ema = pool.cost_ema
            if ema is None:
                ema = lookup_cost(self.cost_table, key)
            cost = (ema if ema is not None else self._DEFAULT_TICK_COST) \
                * pool.pending_ticks()
            slack = (min(deadlines) - now - cost) if deadlines else None
            entries.append((key, slack,
                            max(priorities) if priorities else 0, order))
        entries.sort(key=lambda e: (e[1] is None,
                                    e[1] if e[1] is not None else 0.0,
                                    -e[2], e[3]))
        return [(key, slack) for key, slack, _p, _o in entries]

    def _resolve_completed(self, now: float) -> None:
        # pick up only the tickets this scheduler owns: results submitted to
        # a shared engine out-of-band stay claimable via engine.result()
        done = self.engine.take_completed(self._live.keys())
        for ticket, res in done.items():
            rec = self._live.pop(ticket)
            if (not res.deadline_missed and rec.deadline is not None
                    and now > rec.deadline):
                # finished naturally but late: deliver in full, flagged —
                # never silently late
                res.deadline_missed = True
            latency_ms = (now - rec.submitted) * 1e3
            self.telemetry.observe("scheduler/request_latency",
                                   latency_ms / 1e3)
            self.telemetry.inc("scheduler/completed")
            if res.deadline_missed:
                self.telemetry.inc("scheduler/deadline_missed")
                if rec.future.trace is not None:
                    # flight-record the victim: its full span tree goes into
                    # the telemetry snapshot as a bounded postmortem
                    rt = rec.future.trace
                    self.telemetry.add_postmortem(dict(
                        ticket=ticket, seed=res.request.seed,
                        method=res.request.method,
                        deadline_ms=res.request.deadline_ms,
                        latency_ms=latency_ms,
                        phases_ms=rt.summary()["phases_ms"],
                        tree=self.tracer.request_tree(rt.rid)))
            # resolve before releasing the admission slot: once inflight()
            # reads 0 (drain()'s condition), every future is already done
            rec.future._resolve(res, latency_ms)
            with self._mutex:
                self._inflight -= 1

    def _expire(self, now: float) -> None:
        overdue = [t for t, rec in self._live.items()
                   if rec.deadline is not None and now > rec.deadline]
        for ticket in overdue:
            self.engine.harvest_partial(ticket)

    def _update_gauges(self) -> None:
        tm = self.telemetry
        engine_queued = 0
        for key, pool in self.engine.pools.items():
            label = pool_label(key)
            tm.set_gauge(f"pool/{label}/occupancy", pool.occupancy())
            tm.set_gauge(f"pool/{label}/queued", len(pool.queue))
            engine_queued += len(pool.queue)
        with self._mutex:
            tm.set_gauge("scheduler/inflight", self._inflight)
            tm.set_gauge("scheduler/queue_depth",
                         engine_queued + len(self._admissions))
        for stat in ("promotions", "pools_evicted", "injections",
                     "completed", "partial_harvests", "steps",
                     "status_syncs", "aot_compiles", "aot_cache_hits",
                     "result_cache_hits", "result_cache_misses",
                     "edges_touched", "edge_slots", "backend_compiles"):
            tm.set_gauge(f"engine/{stat}", self.engine.stats[stat])

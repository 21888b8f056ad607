"""Versioned seed→result LRU cache for the serving engine.

At serving scale, hot seeds repeat: the same community query arrives from
many users, and a converged diffusion is a pure function of
``(graph, method, seed, α, ε, statics)``.  This module memoizes those
results so a repeated query returns in O(1) *before admission* — no lane,
no tick, no sweep.

Key design (:func:`result_key`):

  * ``graph_version`` leads the key — callers bump
    :attr:`repro.graphs.handle.GraphHandle.version` when the graph's
    edges change, which makes every cached community stale at once (old
    versions age out of the LRU; no scan-and-purge).
  * The resolved *kernel* backend (ops_backend) is part of the key.  The
    ``pallas`` kernels fold in XLA's combine order, so the two backends
    agree bit for bit where XLA's scatter folds in update order, as on the
    CPU (docs/algorithms.md, guarantee #6); on a TPU that is a measured
    finding, not a guarantee, so an ``xla`` answer never serves a
    ``pallas`` request or the reverse.
  * The *lane* backend is folded to its bit-identity class: dense and dist
    lanes produce bit-identical rows (guarantee #7) and share entries;
    sparse lanes run the sparse update order and key separately
    (guarantee #5 ties them to the *sparse* single-seed driver, not to the
    dense one) — a cached answer must be the exact bits the lane would
    have computed.

Only converged results enter the cache: deadline-missed partials are
best-effort snapshots of an interrupted diffusion, not values of the pure
function.  A hit returns a *copy* whose ``request`` field is the incoming
request (deadlines/priority differ between hits), so callers may mutate
their result without corrupting the cache (guarantee #9: caching never
changes answers).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Optional

__all__ = ["ResultCache", "result_key"]


def result_key(req, lane_backend: str, ops_backend: str,
               graph_version: int = 0) -> tuple:
    """Cache key for one request: ``(graph_version, method, seed, α, ε,
    statics, lane-identity-class, ops_backend)``.  ``lane_backend`` is the
    *resolved* lane type ("dense" | "sparse" | "dist" — never "auto"); dense
    and dist collapse to one class (bit-identical rows, guarantee #7).
    ``ops_backend`` is the resolved kernel backend ("xla" | "pallas")."""
    if req.method == "pr_nibble":
        statics = (req.optimized, req.beta)
    else:
        statics = (req.N, req.t)
    family = "sparse" if lane_backend == "sparse" else "dense"
    return (graph_version, req.method, int(req.seed), float(req.alpha),
            float(req.eps), statics, family, ops_backend)


class ResultCache:
    """Bounded, thread-safe LRU of :class:`ClusterResult` by result key.

    ``get`` counts hits/misses (the engine's ``result_cache_hits`` /
    ``result_cache_misses`` stats and the scheduler's MetricsRegistry
    counters read them); ``put`` refuses deadline-missed partials.  The
    LRU bound is entries, not bytes — a community is O(|cluster|), small by
    the locality of the algorithms being served.
    """

    def __init__(self, capacity: int = 1024) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key: tuple, request=None):
        """The cached :class:`ClusterResult` for ``key`` (marked
        most-recently-used), or None.  The returned result is a fresh copy
        carrying ``request`` (when given) so hit consumers can't alias the
        cached arrays; ``deadline_missed`` is always False on a hit — the
        cached value is the converged answer, delivered instantly."""
        with self._lock:
            res = self._entries.get(key)
            if res is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
        return dataclasses.replace(
            res, request=(request if request is not None else res.request),
            cluster=res.cluster.copy(), deadline_missed=False)

    def put(self, key: tuple, result) -> bool:
        """Insert a *converged* result (partials are rejected — a
        deadline-missed harvest is not the pure function's value).  Returns
        True if stored."""
        if result.deadline_missed:
            return False
        with self._lock:
            self._entries[key] = result
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
        return True

    def invalidate(self) -> None:
        """Drop every entry (graph-version bumps make this unnecessary for
        graph mutations; exposed for tests and manual resets)."""
        with self._lock:
            self._entries.clear()

    def stats(self) -> dict:
        with self._lock:
            return dict(entries=len(self._entries), capacity=self.capacity,
                        hits=self.hits, misses=self.misses,
                        evictions=self.evictions)

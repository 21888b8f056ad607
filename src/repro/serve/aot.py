"""Ahead-of-time compiled tick executables for the serving engine.

The steady-state serving tick must never trace: first-touch jit tracing is
tens-to-hundreds of milliseconds — longer than a typical deadline — and the
jit call path re-checks its cache on every dispatch.  This module lowers
each lane pool's tick kernels (:class:`repro.core.batched.LaneKernels`) to
XLA executables *once*, at pool creation (or eagerly, via
``LocalClusterEngine.warmup``), and caches them per pool key:

  * ``jax.jit(...).lower(...).compile()`` against the pool's exact avals —
    the compiled objects dispatch without re-entering the jit cache and keep
    their ``donate_argnums`` (lane state updates in place);
  * the cache key is the engine's pool key ``(method, backend, statics,
    ops_backend, bucket, topo)``, so a bucket-ladder promotion hops between
    already-compiled executables and an LRU-evicted pool's re-creation is a
    cache hit, never a re-trace;
  * ``compiles`` / ``hits`` / ``compile_seconds`` counters feed the engine's
    ``stats`` dict (and the re-trace-freedom guard in
    tests/test_serve_perf.py).

Every pool's executables carry the pool's name: ``step_<method>_<lane
type>_b<bucket>`` (e.g. ``step_hk_pr_dense_b0``; XLA calls the module
``jit_step_hk_pr_dense_b0``), likewise ``init_``, ``inject_``, ``status_``
and ``sweep_``, with a statics tag appended where two pools of one engine
would otherwise share a name (:meth:`ExecutableCache.get`).  So a profiler
trace tells the pools apart, and every step module — and no other — starts
with ``jit_step``.  When a pool compiles, the ``op_name`` metadata of each
HLO instruction of its optimized modules is read once and kept in a
process-wide table, module name → {instruction name → round phase}
(:func:`op_scopes`): the phase is the outermost of the ``expand`` /
``scatter`` / ``frontier`` scopes of :mod:`repro.core.frontier` the
instruction was traced under, or None.  That lets a trace's op times be
summed by phase.  Names and scopes are metadata: they change no value.

AOT compilation changes *when* programs are built, never what they compute:
the lowered jaxprs are the same ones the jit path would trace, so results
stay bit-identical (docs/algorithms.md, guarantee #9).
"""
from __future__ import annotations

import collections
import re
import threading
import time
import types
from typing import Callable, Dict, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.batched import LaneKernels

__all__ = ["PoolExecutables", "ExecutableCache", "compile_lane_executables",
           "op_scopes", "PHASES"]

# The round phases of repro.core.frontier's named scopes.
PHASES = ("expand", "scatter", "frontier")

_INSTR = re.compile(r"^\s*(ROOT\s+)?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+) ")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")

_scopes_lock = threading.Lock()
_scopes: Dict[str, Dict[str, Optional[str]]] = {}


def _phase(op_name: str) -> Optional[str]:
    """The outermost round phase named in an ``op_name`` path, or None."""
    return next((c for c in op_name.split("/") if c in PHASES), None)


def _record_scopes(hlo_text: str) -> None:
    """Add one optimized module's instruction → phase map to the table.

    An instruction's phase comes from its own ``op_name``.  A fusion the
    compiler left without metadata takes the phase of the computation it
    calls: that of its root, else the most common phase inside it."""
    lines = hlo_text.splitlines()
    m = _MODULE.match(lines[0]) if lines else None
    if m is None:
        return
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, list] = collections.defaultdict(list)
    roots: Dict[str, str] = {}
    comp = None
    for line in lines:
        head = _COMPUTATION.match(line)
        if head is not None:
            comp = head.group(1)
            continue
        hit = _INSTR.match(line)
        if hit is None:
            continue
        name = hit.group(2)
        meta = _OP_NAME.search(line)
        own[name] = _phase(meta.group(1)) if meta else None
        members[comp].append(name)
        if hit.group(1):
            roots[comp] = name
        callee = None if meta else _CALLS.search(line)
        if callee is not None:
            calls[name] = callee.group(1)

    def called_phase(c: str) -> Optional[str]:
        if own.get(roots.get(c)):
            return own[roots[c]]
        found = collections.Counter(own[i] for i in members[c] if own[i])
        return found.most_common(1)[0][0] if found else None

    ops = {name: phase or (called_phase(calls[name]) if name in calls
                           else None)
           for name, phase in own.items()}
    with _scopes_lock:
        _scopes[m.group(1)] = ops


def op_scopes() -> Mapping[str, Mapping[str, Optional[str]]]:
    """Read-only snapshot of the process-wide table: XLA module name (as a
    profiler trace names it, e.g. ``jit_step_hk_pr_dense_b0``) → {HLO
    instruction name (``%fusion.300``) → round phase or None}, for every
    pool compiled in this process.  A module compiled again under the same
    name replaces its entry."""
    with _scopes_lock:
        return types.MappingProxyType(
            {k: types.MappingProxyType(v) for k, v in _scopes.items()})


def _sanitize(text: str) -> str:
    return re.sub(r"_+", "_", re.sub(r"[^A-Za-z0-9_]", "_", text)).strip("_")


def _executable_name(key: tuple, tagged: bool = False) -> str:
    """The name a pool's executables carry, from its pool key ``(method,
    backend, statics, ops_backend, bucket, topo)``: method, lane type and
    bucket; ``tagged`` adds the statics and the kernel backend."""
    method, backend, statics, ops_backend, bucket, _topo = key
    tag = f"_{statics}_{ops_backend}" if tagged else ""
    return _sanitize(f"{method}_{backend}{tag}_b{bucket}")


class PoolExecutables(NamedTuple):
    """AOT-compiled tick entry points for one pool shape.  Same signatures
    as :class:`~repro.core.batched.LaneKernels` (init / inject / step /
    status / sweep), but each is a ``jax`` ``Compiled`` object: calling it
    never traces, and the donated state argument of ``inject``/``step`` is
    consumed (the caller must drop its reference, which the engine does by
    reassigning ``pool.state``)."""
    init: Callable
    inject: Callable
    step: Callable
    status: Callable
    sweep: Callable


def _compile(fn: Callable, name: str, donate: tuple, *avals):
    """jit ``fn`` under ``name`` (the XLA module is ``jit_<name>``), lower
    it against ``avals``, compile, and record the module's op phases."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    compiled = jax.jit(named, donate_argnums=donate).lower(*avals).compile()
    _record_scopes(compiled.as_text())
    return compiled


def compile_lane_executables(kern: LaneKernels, graph, batch_slots: int,
                             name: str) -> PoolExecutables:
    """Lower + compile every kernel of ``kern`` against the pool's avals,
    each under ``<kernel>_<name>`` (``name`` from :func:`_executable_name`).

    ``graph`` is the concrete :class:`~repro.graphs.csr.CSRGraph` the pool
    serves (its arrays contribute avals only — the executables still take
    the graph as a runtime argument).  The lane-state aval comes from
    ``eval_shape`` of the init kernel, so dense/sparse/HK pools all lower
    through this one function.  ``inject`` and ``step`` donate the state.
    """
    B = batch_slots
    seeds = jax.ShapeDtypeStruct((B,), jnp.int32)
    state = jax.eval_shape(kern.init, seeds)
    f32B = jax.ShapeDtypeStruct((B,), jnp.float32)
    boolB = jax.ShapeDtypeStruct((B,), jnp.bool_)
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    return PoolExecutables(
        init=_compile(kern.init, f"init_{name}", (), seeds),
        inject=_compile(kern.inject, f"inject_{name}", (0,), state, i32, i32),
        step=_compile(kern.step, f"step_{name}", (1,),
                      graph, state, f32B, f32B, boolB),
        status=_compile(kern.status, f"status_{name}", (), state),
        sweep=_compile(kern.sweep, f"sweep_{name}", (), graph, state, i32),
    )


class ExecutableCache:
    """Pool-key → :class:`PoolExecutables` cache with compile accounting.

    One instance per engine (the executables close over that engine's graph
    avals and batch width).  ``get`` is locked — the async scheduler's
    drive thread and a caller running ``warmup`` may race pool creation —
    and builds at most once per key.  Evicting a *pool* (device state)
    never evicts its *executables*: compiled programs are small, bounded by
    the O(log) distinct bucket shapes a request stream can produce, and
    keeping them is exactly what makes pool re-creation re-trace-free.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: Dict[tuple, PoolExecutables] = {}
        self._names: Dict[str, tuple] = {}     # executable name → pool key
        self.compiles = 0          # cache misses: full lower+compile builds
        self.hits = 0              # cache hits: reused executable bundles
        self.compile_seconds = 0.0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key: tuple,
            build: Callable[[str], PoolExecutables]) -> PoolExecutables:
        """The executables for ``key``, building (and timing) on first use.
        ``build(name)`` compiles them under the pool's executable name: the
        plain :func:`_executable_name`, or its statics-tagged form when an
        earlier key of this cache already holds the plain one."""
        with self._lock:
            ex = self._entries.get(key)
            if ex is not None:
                self.hits += 1
                return ex
            name = _executable_name(key)
            if self._names.setdefault(name, key) != key:
                name = _executable_name(key, tagged=True)
                self._names[name] = key
            t0 = time.perf_counter()
            ex = build(name)
            self.compile_seconds += time.perf_counter() - t0
            self.compiles += 1
            self._entries[key] = ex
            return ex

    def peek(self, key: tuple) -> Optional[PoolExecutables]:
        with self._lock:
            return self._entries.get(key)

    def stats(self) -> Dict:
        with self._lock:
            return dict(entries=len(self._entries), compiles=self.compiles,
                        hits=self.hits,
                        compile_seconds=self.compile_seconds)

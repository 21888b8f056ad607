"""Unified kernel-dispatch layer for the three hot primitives (``core.ops``).

Every hot loop in the drivers bottoms out in one of three primitives — the
paper's §3 vocabulary, restated as ops:

  ============== ====================================== =====================
  op             paper primitive                        Pallas kernel
  ============== ====================================== =====================
  scatter_add    atomic fetchAdd (batched)              kernels/scatter_accum
  segment_merge  sparse-set batch insert (sort-merge)   kernels/segment_merge
  prefix_sum     prefix sum                             kernels/prefix_scan
  ============== ====================================== =====================

This module is the single seam between the drivers (frontier / sparsevec /
sweep / pr_nibble / batched / distributed / serving) and the kernels: a
driver never names a kernel, it names an op and a *backend*.

Backends
--------
``"xla"``
    The reference: plain jnp/XLA scatter, sort + ``segment_sum``,
    ``jnp.cumsum`` — byte-for-byte the pre-op-layer driver code.
``"pallas"``
    The Pallas kernels, compiled on a TPU and run in the Pallas interpreter
    elsewhere (:func:`repro.kernels.ops.interpret` decides).  ``scatter_add``
    and ``segment_merge`` compute XLA's combine order by construction — a
    left fold per destination / per run in stream order, with no matmul and
    no reassociation — and ``prefix_sum`` is exact for the integer dtypes
    the drivers scan.  So every driver is bit-identical across the two
    backends wherever XLA's scatter folds in update order, as it does on
    CPU (``tests/test_ops.py``).  XLA's f32 scatter on a TPU v5e does not,
    so there the backends can differ in the last bits (``chip_smoke.py``
    reports it; docs/algorithms.md, guarantee #6).  f32 ``prefix_sum``
    reassociates.
``"auto"``
    ``xla`` on every platform: it is the only path whose answers are known
    on every platform.  ``pallas`` runs only when asked for by name.

The ``pallas`` backend has no size bound and hands no part of an op to XLA:
the scatter kernel folds every contribution of a destination group itself,
however many there are (see :func:`repro.kernels.ops.scatter_fold`).  Its
kernels take 32-bit dtypes.

Extending: :func:`register_backend` installs a new named implementation set
without touching any driver — they all take ``backend=`` and pass it here.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops

__all__ = ["OPS", "backends", "register_backend", "resolve",
           "scatter_add", "segment_merge", "prefix_sum",
           "graph_degrees", "graph_expand", "local_csr"]

OPS = ("scatter_add", "segment_merge", "prefix_sum")

_REGISTRY: Dict[str, Dict[str, Callable]] = {}


def register_backend(name: str, **impls) -> None:
    """Register implementations for (a subset of) :data:`OPS` under ``name``.

    Missing ops fall back to the ``xla`` reference, so a backend can swap in
    one kernel at a time."""
    unknown = set(impls) - set(OPS)
    if unknown:
        raise ValueError(f"unknown ops {sorted(unknown)}; valid: {OPS}")
    table = dict(_REGISTRY.get("xla", {}))
    table.update(impls)
    _REGISTRY[name] = table


def backends() -> tuple:
    return tuple(_REGISTRY)


def resolve(backend: str) -> str:
    """Concrete backend name for ``backend`` ("auto" → "xla")."""
    if backend is None or backend == "auto":
        return "xla"
    if backend not in _REGISTRY:
        raise ValueError(
            f"unknown ops backend {backend!r}; registered: {backends()}")
    return backend


def _impl(op: str, backend: str) -> Callable:
    return _REGISTRY[resolve(backend)][op]


# ------------------------------------------------------------------- the ops

def scatter_add(vec, idx, vals, valid=None, *, backend: str = "xla"):
    """Masked ``vec.at[idx].add(vals)`` — the batched fetchAdd.

    ``valid`` masks both the index (dropped via the shared sentinel
    ``vec.shape[0]``) and the value; ``None`` means all valid.  The result
    keeps ``vec``'s dtype (any dtype on ``xla``, 32-bit on ``pallas``).
    Backends agree bitwise where XLA folds in update order (see module
    docstring)."""
    if valid is None:
        valid = jnp.ones(idx.shape, bool)
    return _impl("scatter_add", backend)(vec, idx, vals, valid)


def segment_merge(ids, vals, n: int, cap: int, *, backend: str = "xla"):
    """Sum duplicate ids of an unsorted stream; compact to ``cap`` slots.

    ``ids`` int32[tot] with sentinel ``n`` marking dropped entries, ``vals``
    f32[tot].  Returns ``(out_ids int32[cap], out_vals f32[cap],
    count int32)`` — unique ids ascending, per-id totals folded in stream
    order, sentinel/zero padded; ``count`` is uncapped so callers detect
    overflow as ``count > cap``.  This is the body of
    :func:`repro.core.sparsevec.sv_merge_add`."""
    return _impl("segment_merge", backend)(ids, vals, n, cap)


def prefix_sum(x, *, backend: str = "xla"):
    """Inclusive prefix sum, dtype preserved (int scans are exact on every
    backend; f32 scans may reassociate on ``pallas``)."""
    return _impl("prefix_sum", backend)(x)


# ------------------------------------------------------- the graph seam
# Host-level drivers stop assuming a resident CSR: they ask these dispatchers,
# which accept any graph-like (CSRGraph | PartitionedCSR | GraphHandle — see
# repro.graphs.handle) and route to the representation that can answer.
# Imports are lazy: frontier.py imports this module, and the graphs package
# must stay importable without core.

def graph_degrees(graph):
    """Host int32[n] degree vector of any graph-like, without materializing a
    resident CSR (partition slabs already carry degrees)."""
    from repro.graphs.handle import as_handle
    return as_handle(graph).degrees()


def local_csr(graph):
    """The resident-CSR view of any graph-like (materialized + cached from
    the partition slabs when the handle was built sharded-first)."""
    from repro.graphs.handle import as_local_csr
    return as_local_csr(graph)


def graph_expand(graph, frontier, cap_e: int, *, backend: str = "xla"):
    """Neighborhood expansion (EDGEMAP) of ``frontier`` against any
    graph-like.  Local handles route to :func:`repro.core.frontier.expand`;
    a sharded-only handle raises — per-shard expansion belongs to the
    distributed drivers (`repro.core.batched_dist` /
    `repro.core.distributed`), which own the exchange collective."""
    from repro.graphs.handle import as_handle
    from .frontier import expand
    handle = as_handle(graph)   # coerce first: bare PartitionedCSR included
    if handle.is_sharded and not handle.has_local:
        raise ValueError(
            "graph_expand needs a resident CSR; this graph is sharded-only "
            "— use the distributed drivers, or handle.local() to gather")
    return expand(handle.local(), frontier, cap_e, backend=backend)


# ------------------------------------------------------------ xla (reference)

def _scatter_add_xla(vec, idx, vals, valid):
    safe = jnp.where(valid, idx, vec.shape[0])
    return vec.at[safe].add(jnp.where(valid, vals, 0).astype(vec.dtype),
                            mode="drop")


def _segment_merge_xla(ids, vals, n, cap):
    # sort → adjacent-duplicate groups → segment_sum → prefix-sum compaction:
    # verbatim the pre-op-layer sv_merge_add body (the bit-identity reference)
    tot = ids.shape[0]
    order = jnp.argsort(ids)
    ids_s = ids[order]
    vals_s = vals[order]
    first = jnp.concatenate([jnp.array([True]), ids_s[1:] != ids_s[:-1]])
    group = jnp.cumsum(first) - 1
    sums = jax.ops.segment_sum(vals_s, group, num_segments=tot)
    sel = first & (ids_s < n)
    pos = jnp.cumsum(sel) - 1
    count = jnp.sum(sel).astype(jnp.int32)
    out_ids = jnp.full((cap,), n, jnp.int32).at[
        jnp.where(sel, pos, cap)].set(ids_s, mode="drop")
    out_vals = jnp.zeros((cap,), jnp.float32).at[
        jnp.where(sel, pos, cap)].set(sums[group], mode="drop")
    return out_ids, out_vals, count


def _prefix_sum_xla(x):
    return jnp.cumsum(x)


register_backend("xla",
                 scatter_add=_scatter_add_xla,
                 segment_merge=_segment_merge_xla,
                 prefix_sum=_prefix_sum_xla)


# ------------------------------------------------------------------- pallas

def _scatter_add_pallas(vec, idx, vals, valid):
    safe = jnp.where(valid, idx, vec.shape[0])
    return kops.scatter_fold(vec, safe, jnp.where(valid, vals, 0))


def _segment_merge_pallas(ids, vals, n, cap):
    order = jnp.argsort(ids)                 # same stable sort as xla
    return kops.segment_merge_sorted(ids[order], vals[order], n, cap)


register_backend("pallas",
                 scatter_add=_scatter_add_pallas,
                 segment_merge=_segment_merge_pallas,
                 prefix_sum=kops.prefix_sum)

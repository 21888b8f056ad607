"""GraphHandle — the single graph-carrying contract across every layer.

Before this abstraction each layer hard-coded *which* physical graph
representation it consumed: the drivers and the serving engine demanded a
resident :class:`~repro.graphs.csr.CSRGraph`, while the distributed engine
took a bare :class:`~repro.graphs.partition.PartitionedCSR` plus a mesh —
which made the sharded path a dead end off the serving path.  ``GraphHandle``
is the tagged union over both:

  * **local**       — a device-resident ``CSRGraph`` (the single-chip case);
  * **partitioned** — a ``PartitionedCSR`` (row slabs stacked on a leading
    device axis) together with the mesh/axis it is sharded over, optionally
    *alongside* the local CSR it was partitioned from.

Callers ask the handle questions (``n``, ``m``, ``degrees()``,
``is_sharded``, ``num_shards``) instead of reaching into a representation,
and materialize the representation they need on demand:

  * :meth:`GraphHandle.local` returns the resident CSR — reconstructing it
    host-side from the partition slabs (and caching it) if the handle was
    built sharded-first.  Sweep cuts and the dense/sparse lane pools go
    through here.
  * :meth:`GraphHandle.partitioned` returns the ``PartitionedCSR`` —
    partitioning the local CSR over the handle's mesh axis on first use (and
    caching).  The distributed drivers (`repro.core.distributed`,
    `repro.core.batched_dist`) go through here.

Every public driver accepts either a raw ``CSRGraph`` or a ``GraphHandle``
(coerced via :func:`as_handle` / :func:`as_local_csr`), so single-chip call
sites are unchanged while sharded graphs flow through the same signatures.

``n`` is always the *true* (unpadded) vertex count: the partition pads the
last shard with isolated sentinel vertices (see
`repro.graphs.partition.PartitionedCSR` padding contract) and the handle is
where that padding is made invisible — distributed state vectors of length
``n_pad`` are sliced back to ``n`` before they reach any consumer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .csr import CSRGraph
from .partition import PartitionedCSR, partition_rows

__all__ = ["GraphHandle", "as_handle", "as_local_csr"]


class GraphHandle:
    """Tagged union over local / partitioned graph representations.

    Build with :meth:`from_csr`, :meth:`from_partitioned`, or :meth:`shard`;
    or coerce anything graph-like with :func:`as_handle`.
    """

    def __init__(self, *, csr: Optional[CSRGraph] = None,
                 pg: Optional[PartitionedCSR] = None,
                 mesh: Any = None, axis: str = "data"):
        if csr is None and pg is None:
            raise ValueError("GraphHandle needs a CSRGraph or a PartitionedCSR")
        self._csr = csr
        self._pg = None if pg is None else _place(pg, mesh, axis)
        self.mesh = mesh
        self.axis = axis
        # Monotonic content version: the serving layer's seed→result cache
        # (repro.serve.result_cache) keys on it, so bumping it on any edge
        # mutation invalidates every cached community at once.  The handle
        # owns it because the handle is the graph-identity contract — both
        # representations (csr, pg) describe one logical graph at one
        # version.
        self.version = 0

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_csr(cls, csr: CSRGraph) -> "GraphHandle":
        """Local (single-chip) handle."""
        return cls(csr=csr)

    @classmethod
    def from_partitioned(cls, pg: PartitionedCSR, mesh: Any = None,
                         axis: str = "data",
                         csr: Optional[CSRGraph] = None) -> "GraphHandle":
        """Sharded handle; ``csr`` optionally carries the source graph so
        :meth:`local` is free instead of a host-side reconstruction."""
        return cls(csr=csr, pg=pg, mesh=mesh, axis=axis)

    @classmethod
    def shard(cls, csr: CSRGraph, mesh: Any,
              axis: str = "data") -> "GraphHandle":
        """Partition a local CSR over ``mesh``'s ``axis`` (kept alongside)."""
        pg = partition_rows(csr, int(mesh.shape[axis]))
        return cls(csr=csr, pg=pg, mesh=mesh, axis=axis)

    # -- tag / shape questions ----------------------------------------------

    @property
    def kind(self) -> str:
        return "partitioned" if self._pg is not None else "local"

    @property
    def is_sharded(self) -> bool:
        return self._pg is not None

    @property
    def has_local(self) -> bool:
        """True when a resident CSR is already materialized."""
        return self._csr is not None

    @property
    def num_shards(self) -> int:
        return self._pg.num_shards if self._pg is not None else 1

    @property
    def n(self) -> int:
        """True (unpadded) vertex count."""
        if self._csr is not None:
            return self._csr.n
        return self._pg.n_true

    @property
    def n_pad(self) -> int:
        """Padded vertex count of the sharded layout (== n when local)."""
        return self._pg.n if self._pg is not None else self._csr.n

    @property
    def m(self) -> int:
        return (self._csr or self._pg).m

    @property
    def total_volume(self) -> int:
        return 2 * self.m

    def degrees(self) -> np.ndarray:
        """Host int32[n] degree vector — available for either tag without
        materializing a CSR (the partition slabs already carry degrees)."""
        if self._csr is not None:
            return np.asarray(self._csr.deg)
        return np.asarray(self._pg.deg).reshape(-1)[: self.n]

    def bump_version(self) -> int:
        """Advance the content version (call after mutating the graph the
        handle wraps).  Serving-layer result caches key on the version, so
        stale communities can never be served after a bump."""
        self.version += 1
        return self.version

    def require_mesh(self):
        if self.mesh is None:
            raise ValueError(
                "this GraphHandle is sharded but carries no mesh; build it "
                "with GraphHandle.shard(csr, mesh) or from_partitioned(pg, "
                "mesh=...) to use the distributed drivers")
        return self.mesh

    # -- representation materializers ---------------------------------------

    def local(self) -> CSRGraph:
        """The resident CSR, reconstructed from the partition slabs (host
        side, cached) when the handle was built sharded-first."""
        if self._csr is None:
            self._csr = _gather_csr(self._pg)
        return self._csr

    def partitioned(self, num_shards: Optional[int] = None) -> PartitionedCSR:
        """The row-sharded slabs, partitioning the local CSR on first use.
        ``num_shards`` defaults to the mesh axis size."""
        if self._pg is None:
            if num_shards is None:
                num_shards = int(self.require_mesh().shape[self.axis])
            self._pg = _place(partition_rows(self._csr, num_shards),
                              self.mesh, self.axis)
        elif num_shards is not None and num_shards != self._pg.num_shards:
            raise ValueError(
                f"handle is partitioned over {self._pg.num_shards} shards, "
                f"requested {num_shards}")
        return self._pg

    def __repr__(self) -> str:
        tag = (f"partitioned[{self.num_shards}x{self._pg.rows_per}]"
               if self.is_sharded else "local")
        return f"GraphHandle({tag}, n={self.n}, m={self.m})"


def _place(pg: PartitionedCSR, mesh: Any, axis: str) -> PartitionedCSR:
    """Put slab ``d`` of every per-shard array on the ``d``-th device of
    ``mesh``'s ``axis``, so the sharded drivers find their shards in place
    instead of moving the whole graph from one device on every call.
    Without a mesh (or with one of another width) the slabs stay where
    they are."""
    if mesh is None or int(mesh.shape[axis]) != pg.num_shards:
        return pg
    rows = NamedSharding(mesh, PartitionSpec(axis))
    return dataclasses.replace(pg, indptr=jax.device_put(pg.indptr, rows),
                               indices=jax.device_put(pg.indices, rows),
                               deg=jax.device_put(pg.deg, rows))


def _gather_csr(pg: PartitionedCSR) -> CSRGraph:
    """Rebuild the global CSR from per-shard slabs (columns are global ids
    already; padded sentinel rows are dropped)."""
    deg = np.asarray(pg.deg).reshape(-1)[: pg.n_true].astype(np.int32)
    indptr = np.zeros(pg.n_true + 1, dtype=np.int32)
    np.cumsum(deg, out=indptr[1:])
    slabs = []
    host_indptr = np.asarray(pg.indptr)
    host_indices = np.asarray(pg.indices)
    for d in range(pg.num_shards):
        slabs.append(host_indices[d, : int(host_indptr[d, -1])])
    indices = (np.concatenate(slabs) if slabs
               else np.zeros(0, np.int32)).astype(np.int32)
    return CSRGraph(indptr=jnp.asarray(indptr), indices=jnp.asarray(indices),
                    deg=jnp.asarray(deg), n=int(pg.n_true), m=int(pg.m))


def as_handle(graph, mesh: Any = None, axis: str = "data") -> GraphHandle:
    """Coerce anything graph-like into a :class:`GraphHandle`.

    ``CSRGraph`` → local handle (sharded over ``mesh`` when one is given);
    ``PartitionedCSR`` → partitioned handle; an existing handle passes
    through unchanged — unless a ``mesh`` is given and the handle has none,
    in which case a *new* handle is returned (sharing the cached
    representations, never mutating the caller's object).  A ``mesh`` that
    conflicts with the handle's own is an error, not a silent override.
    """
    if isinstance(graph, GraphHandle):
        if mesh is None:
            return graph
        if graph.mesh is None:
            return GraphHandle(csr=graph._csr, pg=graph._pg,
                               mesh=mesh, axis=axis)
        if graph.mesh != mesh or graph.axis != axis:
            raise ValueError(
                f"mesh/axis conflict: handle carries {graph.mesh} over "
                f"{graph.axis!r}, caller passed {mesh} over {axis!r} — "
                f"build a fresh handle for a different topology")
        return graph
    if isinstance(graph, PartitionedCSR):
        return GraphHandle.from_partitioned(graph, mesh=mesh, axis=axis)
    if isinstance(graph, CSRGraph):
        if mesh is not None:
            return GraphHandle.shard(graph, mesh, axis)
        return GraphHandle.from_csr(graph)
    raise TypeError(f"expected CSRGraph | PartitionedCSR | GraphHandle, "
                    f"got {type(graph).__name__}")


def as_local_csr(graph) -> CSRGraph:
    """The resident-CSR view of anything graph-like (see :func:`as_handle`)."""
    if isinstance(graph, CSRGraph):
        return graph
    return as_handle(graph).local()

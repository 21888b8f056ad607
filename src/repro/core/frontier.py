"""Fixed-capacity frontier machinery — the TPU-native Ligra.

Ligra's ``vertexSubset`` + ``EDGEMAP`` do work proportional to the active
vertices and their edges using dynamic queues and atomics.  Under XLA all
shapes are static, so the same *work-locality* is obtained with:

  * ``Frontier``      — a padded id buffer ``ids[cap]`` + ``count``; invalid
                        slots hold the sentinel ``n`` (one-past-last vertex).
  * ``expand``        — EDGEMAP's edge enumeration: exclusive prefix-sum over
                        frontier degrees, then each of the ``cap_e`` edge slots
                        finds its (frontier slot, within-row offset) with a
                        ``searchsorted`` — O(cap_e log cap_f) work,
                        O(log) depth: exactly the paper's §3 primitives.
  * ``pack_unique``   — the new-frontier ``filter``: sort candidates, mask
                        duplicates + failed predicate, prefix-sum compaction.
  * ``compact_sorted`` — the same ``filter`` over ids that are already
                        sorted and unique (a SparseVec's support): mask +
                        prefix-sum compaction, no sort.  The sparse
                        PR-Nibble round takes its next frontier this way
                        from ``r``'s own slots and packs no candidates.

Overflow (frontier or edge workspace exceeding capacity) is detected exactly
and surfaced as a flag; drivers retry at the next power-of-two bucket
(`bucketed recompilation` — the static-shape analogue of queue growth, at most
O(log) recompiles per graph).

All functions are pure jnp and usable under jit / vmap / shard_map.  Each
round phase runs under a ``jax.named_scope`` — ``expand`` (frontier → edge
list), ``scatter`` (accumulating into p and r) and ``frontier`` (threshold
test and compaction) — so every HLO operation of a compiled round carries
its phase in its ``op_name`` metadata (serve/aot.py maps profiled ops back
to it).  Scopes are metadata only: they change no computation.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from . import ops

__all__ = ["Frontier", "EdgeBatch", "singleton", "expand", "pack_unique",
           "compact_sorted", "next_pow2", "DEFAULT_CAPS", "scatter_add_dense",
           "scatter_set_dense", "one_hot_f32"]

DEFAULT_CAPS = dict(cap_f=1 << 12, cap_e=1 << 16)


class Frontier(NamedTuple):
    ids: jnp.ndarray       # int32[cap_f]; invalid slots == sentinel (n)
    count: jnp.ndarray     # int32 scalar — number of valid slots (prefix)
    overflow: jnp.ndarray  # bool scalar — capacity was exceeded

    @property
    def cap(self) -> int:
        return self.ids.shape[0]

    def valid(self) -> jnp.ndarray:
        return jnp.arange(self.ids.shape[0], dtype=jnp.int32) < self.count


class EdgeBatch(NamedTuple):
    """Result of expanding a frontier: one slot per (frontier vertex, edge)."""
    slot: jnp.ndarray      # int32[cap_e] — index into frontier ids
    src: jnp.ndarray       # int32[cap_e] — source vertex id (sentinel if invalid)
    dst: jnp.ndarray       # int32[cap_e] — destination vertex id (sentinel if invalid)
    valid: jnp.ndarray     # bool [cap_e]
    total: jnp.ndarray     # int32 scalar — true number of edges
    overflow: jnp.ndarray  # bool scalar


def next_pow2(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def singleton(v, n: int, cap_f: int) -> Frontier:
    """Frontier containing exactly the seed vertex (paper line 9)."""
    ids = jnp.full((cap_f,), n, dtype=jnp.int32).at[0].set(jnp.asarray(v, jnp.int32))
    return Frontier(ids=ids, count=jnp.asarray(1, jnp.int32),
                    overflow=jnp.asarray(False))


def seed_set(vs: jnp.ndarray, count, n: int, cap_f: int) -> Frontier:
    """Frontier from a multi-vertex seed set (paper footnote 3: "Our codes
    can easily be modified to take as input a seed set with multiple
    vertices"), sentinel-padded to cap_f."""
    vs = jnp.asarray(vs, jnp.int32)
    k = vs.shape[0]
    valid = jnp.arange(k, dtype=jnp.int32) < count
    ids = jnp.full((cap_f,), n, dtype=jnp.int32)
    ids = ids.at[jnp.where(valid, jnp.arange(k), cap_f)].set(
        jnp.where(valid, vs, n), mode="drop")
    return Frontier(ids=ids, count=jnp.asarray(count, jnp.int32),
                    overflow=jnp.asarray(k > cap_f))


@jax.named_scope("expand")
def expand(graph: CSRGraph, frontier: Frontier, cap_e: int,
           backend: str = "xla") -> EdgeBatch:
    """Enumerate all edges incident to the frontier into ``cap_e`` slots.

    Work O(cap_e log cap_f), depth O(log) — matches EDGEMAP's
    work-proportional-to-outgoing-edges contract.  ``backend`` routes the
    degree prefix sum through :mod:`repro.core.ops` (int32 — exact on every
    backend).
    """
    n = graph.n
    fvalid = frontier.valid()
    ids = jnp.where(fvalid, frontier.ids, n)
    degs = jnp.where(fvalid, graph.deg[jnp.minimum(ids, n - 1)], 0)
    degs = jnp.where(ids < n, degs, 0).astype(jnp.int32)
    offs = ops.prefix_sum(degs, backend=backend) - degs  # exclusive prefix sum
    total = offs[-1] + degs[-1]
    j = jnp.arange(cap_e, dtype=jnp.int32)
    # frontier slot owning edge slot j: last i with offs[i] <= j
    slot = jnp.searchsorted(offs, j, side="right").astype(jnp.int32) - 1
    slot = jnp.clip(slot, 0, frontier.cap - 1)
    within = j - offs[slot]
    valid = j < total
    src = jnp.where(valid, ids[slot], n)
    base = graph.indptr[jnp.minimum(src, n - 1)]
    eidx = jnp.clip(base + within, 0, graph.indices.shape[0] - 1)
    dst = jnp.where(valid, graph.indices[eidx], n)
    return EdgeBatch(slot=slot, src=src, dst=dst, valid=valid, total=total,
                     overflow=total > cap_e)


@jax.named_scope("frontier")
def pack_unique(cands: jnp.ndarray, keep: jnp.ndarray, n: int,
                cap_out: int, backend: str = "xla") -> Frontier:
    """Filter + dedupe candidate vertex ids into a fresh frontier.

    ``cands`` may contain duplicates and sentinel entries; ``keep`` is the
    predicate mask (evaluated by the caller, e.g. ``p[v] >= d(v)*eps``).
    Sort → adjacent-duplicate mask → prefix-sum compaction: O(C log C) work,
    O(log C) depth (paper §3's sort+filter).
    """
    x = jnp.where(keep, cands, n).astype(jnp.int32)
    xs = jnp.sort(x)
    first = jnp.concatenate([jnp.array([True]), xs[1:] != xs[:-1]])
    sel = first & (xs < n)
    pos = ops.prefix_sum(sel.astype(jnp.int32), backend=backend) - 1
    count = jnp.sum(sel).astype(jnp.int32)
    out = jnp.full((cap_out,), n, dtype=jnp.int32)
    # drop writes beyond capacity; overflow flag reports the truncation
    out = out.at[jnp.where(sel, pos, cap_out)].set(xs, mode="drop")
    return Frontier(ids=out, count=jnp.minimum(count, cap_out),
                    overflow=count > cap_out)


@jax.named_scope("frontier")
def compact_sorted(ids: jnp.ndarray, keep: jnp.ndarray, n: int,
                   cap_out: int, backend: str = "xla") -> Frontier:
    """Filter already sorted, duplicate-free ids into a fresh frontier.

    ``ids`` is ascending with sentinel ``n`` padding (a SparseVec's
    ``ids``); ``keep`` is the predicate mask.  Prefix-sum compaction into
    ``cap_out`` slots: O(C) work, O(log C) depth, no sort.  The output
    contract is :func:`pack_unique`'s on the same input — ids ascending,
    sentinel padded, ``count = min(k, cap_out)``, ``overflow = k > cap_out``
    for ``k`` kept ids.
    """
    sel = keep & (ids < n)
    pos = ops.prefix_sum(sel.astype(jnp.int32), backend=backend) - 1
    count = jnp.sum(sel).astype(jnp.int32)
    out = jnp.full((cap_out,), n, dtype=jnp.int32)
    # drop writes beyond capacity; overflow flag reports the truncation
    out = out.at[jnp.where(sel, pos, cap_out)].set(ids, mode="drop")
    return Frontier(ids=out, count=jnp.minimum(count, cap_out),
                    overflow=count > cap_out)


@jax.named_scope("scatter")
def scatter_add_dense(vec: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
                      valid: jnp.ndarray, backend: str = "xla") -> jnp.ndarray:
    """fetchAdd → scatter-add: accumulate ``vals`` at ``idx`` (masked).

    Deterministic on every backend (XLA scatter-add has a defined combine
    order; the Pallas kernel folds in submission order — see
    :mod:`repro.core.ops`), replacing the paper's atomic fetch-and-add.
    """
    return ops.scatter_add(vec, idx, vals, valid, backend=backend)


@jax.named_scope("scatter")
def scatter_set_dense(vec: jnp.ndarray, idx: jnp.ndarray, vals,
                      valid: jnp.ndarray) -> jnp.ndarray:
    """Masked ``vec.at[idx].set(vals)`` with the shared drop-sentinel
    convention (invalid lanes write nowhere).  Scatter-*set* has no combine,
    so it has no backend axis — this helper exists so driver code stays free
    of raw ``.at[`` sites outside ops.py/frontier.py."""
    safe = jnp.where(valid, idx, vec.shape[0])
    return vec.at[safe].set(jnp.where(valid, vals, jnp.zeros_like(vals)),
                            mode="drop")


def one_hot_f32(x, n: int) -> jnp.ndarray:
    """f32[n] with a single 1.0 at vertex ``x`` — the unit seed mass every
    dense diffusion starts from."""
    return jnp.zeros((n,), jnp.float32).at[x].set(1.0)

"""Sort-merge sparse vectors — the TPU-native replacement for the paper's
concurrent hash table (§3 "Sparse Sets").

The paper stores (vertex → value) in a lock-free linear-probing hash table;
its complexity analysis only needs batched insert/lookup in O(N) work and
O(log N) depth.  On a TPU random probing is hostile, but *sort* is a native
primitive — so a sparse set here is a sorted, sentinel-padded
``(ids, vals)`` pair:

  * lookup  — ``searchsorted`` (O(log cap) per query, vectorized)
  * merge-add — concatenate + sort + adjacent-segment-sum + compaction
    (O((cap+U) log) work, O(log) depth for U updates — the same bounds as a
    batch of hash inserts, and deterministic)

Capacity is static per jit bucket; exceeding it raises the overflow flag and
the driver retries one bucket up (see frontier.py).

The merge-add reduction itself (sort → sum-duplicates → compact) is an op:
it dispatches through :func:`repro.core.ops.segment_merge`, so ``backend=
"pallas"`` folds each run on the segment-merge kernel
(kernels/segment_merge.py) in the XLA reference's order.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import ops
from .frontier import scatter_set_dense

__all__ = ["SparseVec", "sv_empty", "sv_lookup", "sv_merge_add",
           "sv_update_existing", "sv_from_pairs"]


class SparseVec(NamedTuple):
    ids: jnp.ndarray       # int32[cap] — sorted; sentinel (n) padded
    vals: jnp.ndarray      # f32[cap]
    count: jnp.ndarray     # int32
    overflow: jnp.ndarray  # bool

    @property
    def cap(self) -> int:
        return self.ids.shape[0]

    def valid(self) -> jnp.ndarray:
        return jnp.arange(self.cap, dtype=jnp.int32) < self.count


def sv_empty(cap: int, n: int) -> SparseVec:
    return SparseVec(ids=jnp.full((cap,), n, jnp.int32),
                     vals=jnp.zeros((cap,), jnp.float32),
                     count=jnp.asarray(0, jnp.int32),
                     overflow=jnp.asarray(False))


def sv_from_pairs(ids, vals, valid, cap: int, n: int,
                  backend: str = "xla") -> SparseVec:
    """Build from (possibly duplicated / unsorted) pairs: duplicates summed."""
    return sv_merge_add(sv_empty(cap, n), ids, vals, valid, n,
                        backend=backend)


def sv_lookup(sv: SparseVec, queries: jnp.ndarray, n: int) -> jnp.ndarray:
    """vals for each query id; 0.0 where absent (the paper's ⊥ = 0)."""
    pos = jnp.searchsorted(sv.ids, queries)
    pos = jnp.clip(pos, 0, sv.cap - 1)
    hit = (sv.ids[pos] == queries) & (queries < n)
    return jnp.where(hit, sv.vals[pos], 0.0)


@jax.named_scope("scatter")
def sv_update_existing(sv: SparseVec, ids, new_vals, valid) -> SparseVec:
    """Overwrite values of keys already present (no structural change)."""
    pos = jnp.clip(jnp.searchsorted(sv.ids, ids), 0, sv.cap - 1)
    hit = valid & (sv.ids[pos] == ids)
    return sv._replace(vals=scatter_set_dense(sv.vals, pos, new_vals, hit))


@jax.named_scope("scatter")
def sv_merge_add(sv: SparseVec, upd_ids, upd_vals, upd_valid, n: int,
                 backend: str = "xla") -> SparseVec:
    """`r[w] += delta` for a batch of updates — the fetchAdd batch.

    Concatenate the live entries with the updates, then one
    :func:`repro.core.ops.segment_merge`: sort by id, sum adjacent duplicates,
    compact back to `cap`.
    """
    cap = sv.cap
    ids_all = jnp.concatenate([
        jnp.where(sv.valid(), sv.ids, n),
        jnp.where(upd_valid, upd_ids, n).astype(jnp.int32)])
    vals_all = jnp.concatenate([
        jnp.where(sv.valid(), sv.vals, 0.0),
        jnp.where(upd_valid, upd_vals, 0.0)])
    out_ids, out_vals, new_count = ops.segment_merge(ids_all, vals_all, n,
                                                     cap, backend=backend)
    return SparseVec(ids=out_ids, vals=out_vals,
                     count=jnp.minimum(new_count, cap),
                     overflow=sv.overflow | (new_count > cap))

"""Layout wrappers around the Pallas kernels.

These own the layout work (sort-and-group, padding, run flags, compaction)
so callers deal in vector and stream terms.  :func:`interpret` is the one
place that decides whether the kernels run compiled or in the Pallas
interpreter: compiled on a TPU, interpreted everywhere else (the kernels'
parity tests run on CPU that way).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import prefix_scan, scatter_accum, segment_merge

__all__ = ["on_tpu", "interpret", "scatter_fold", "prefix_sum",
           "segment_merge_sorted"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Run the kernels in the Pallas interpreter?  Never on a TPU."""
    return not on_tpu()


def _check_32bit(x) -> None:
    if jnp.dtype(x.dtype).itemsize != 4:
        raise TypeError(f"the Pallas kernels take 32-bit dtypes, got {x.dtype}")


def scatter_fold(vec: jnp.ndarray, idx: jnp.ndarray,
                 vals: jnp.ndarray) -> jnp.ndarray:
    """``vec.at[idx].add(vals, mode="drop")`` folded in submission order.

    Every destination receives its contributions as the left fold
    ``((vec[i] + v_1) + v_2) + …`` in the order they appear in ``idx``
    (stable sort), however many there are, so the result equals an XLA
    scatter that combines in update order, bit for bit.  Nothing is handed
    to XLA.  Indices outside ``[0, n)`` are dropped.
    ``vec`` is any 32-bit dtype; ``vals`` is cast to it."""
    _check_32bit(vec)
    n, m = vec.shape[0], idx.shape[0]
    if m == 0:
        return vec
    G, B = scatter_accum.GROUP, scatter_accum.BLOCK
    groups = -(-n // G)
    key = jnp.where((idx >= 0) & (idx < n), idx, n).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)   # keeps submission order per key
    key_s = key[order]
    # group g's contributions are stream elements bounds[g] … bounds[g+1]-1
    # (dropped ones sort last, past bounds[groups])
    bounds = jnp.searchsorted(
        key_s, jnp.minimum(jnp.arange(groups + 1, dtype=jnp.int32) * G, n),
        side="left").astype(jnp.int32)
    pad = -m % B
    dest = jnp.pad(key_s % G, (0, pad)).reshape(-1, scatter_accum.CHUNK)
    v = jnp.pad(vals[order].astype(vec.dtype), (0, pad)).reshape(
        -1, scatter_accum.CHUNK)
    vec2d = jnp.pad(vec, (0, groups * G - n)).reshape(-1, scatter_accum.LANES)
    return scatter_accum.scatter_fold_groups(
        bounds[None], dest, v, vec2d, interpret=interpret()).reshape(-1)[:n]


def prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum through the blocked scan kernel, dtype
    preserved: int32 results equal ``jnp.cumsum`` bit for bit; f32 scans
    reassociate."""
    _check_32bit(x)
    n = x.shape[0]
    if n == 0:
        return x
    pad = -n % prefix_scan.BLOCK
    x2d = jnp.pad(x, (0, pad)).reshape(-1, prefix_scan.LANES)
    return prefix_scan.block_scan(x2d, interpret=interpret()).reshape(-1)[:n]


def segment_merge_sorted(ids_s, vals_s, n: int, cap: int):
    """Sum duplicate runs of a *sorted* id stream and compact to ``cap``.

    Args:
      ids_s:  int32[tot] sorted ascending; entries ≥ ``n`` are sentinels.
      vals_s: f32[tot] values aligned with ``ids_s``.
      n:      sentinel threshold (one past the last valid id).
      cap:    output capacity.
    Returns:
      ``(out_ids int32[cap], out_vals f32[cap], count int32)`` — unique ids
      ascending with per-id totals folded in stream order, sentinel-``n`` /
      zero padded; ``count`` is the *uncapped* number of unique ids.  The
      output contract of :func:`repro.core.ops.segment_merge`.
    """
    tot = ids_s.shape[0]
    step = segment_merge.SUB * segment_merge.BLK
    pad = -tot % step if tot else step
    ids_p = jnp.concatenate([ids_s.astype(jnp.int32),
                             jnp.full((pad,), n, jnp.int32)])
    vals_p = jnp.concatenate([vals_s.astype(jnp.float32),
                              jnp.zeros((pad,), jnp.float32)])
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), ids_p[:-1]])
    nxt = jnp.concatenate([ids_p[1:], jnp.full((1,), -2, jnp.int32)])
    first = (ids_p != prev).astype(jnp.int32)
    keep = (ids_p != nxt) & (ids_p < n)
    run = segment_merge.fold_runs(
        first.reshape(-1, segment_merge.BLK),
        vals_p.reshape(-1, segment_merge.BLK),
        interpret=interpret()).reshape(-1)
    rank = prefix_sum(keep.astype(jnp.int32))
    count = rank[-1]
    at = jnp.where(keep, rank - 1, cap)
    out_ids = jnp.full((cap,), n, jnp.int32).at[at].set(ids_p, mode="drop")
    out_vals = jnp.zeros((cap,), jnp.float32).at[at].set(run, mode="drop")
    return out_ids, out_vals, count

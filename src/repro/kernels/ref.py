"""Plain oracles for the kernels and ops (the correctness contracts).

Each function computes the same mathematical object as a kernel or op with
host numpy loops or plain jax.numpy — no tiling, no sorting pipeline — and
is what the parity tests assert against (``tests/test_kernels.py``,
``tests/test_ops.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["scatter_add_ref", "segment_merge_ref", "fold_runs_ref"]


def scatter_add_ref(vec, idx, vals, valid):
    """Masked scatter-add oracle for :func:`repro.core.ops.scatter_add`,
    structure-free: a host-side numpy left fold over the updates in
    submission order — the exact combine order both backends must
    reproduce, computed without any scatter/sort machinery.  Test-only
    (eager numpy, not jit-able)."""
    import numpy as np
    out = np.asarray(vec).copy()
    idx = np.asarray(idx)
    vals = np.asarray(vals).astype(out.dtype)
    valid = np.asarray(valid)
    for j in range(idx.shape[0]):
        if valid[j] and 0 <= idx[j] < out.shape[0]:
            out[idx[j]] += vals[j]
    return out


def segment_merge_ref(ids, vals, n: int, cap: int):
    """Duplicate-summing merge oracle for
    :func:`repro.core.ops.segment_merge`: a dense scatter-accumulate over the
    full id range followed by a top-``cap`` extraction of the support —
    no sorting pipeline at all, so it shares no structure with either
    backend implementation."""
    dense = jnp.zeros((n + 1,), jnp.float32).at[
        jnp.clip(ids, 0, n)].add(jnp.where(ids < n, vals, 0.0))
    hit = jnp.zeros((n + 1,), bool).at[jnp.clip(ids, 0, n)].set(ids < n)
    present = hit[:n]
    count = jnp.sum(present).astype(jnp.int32)
    pos = jnp.cumsum(present) - 1
    out_ids = jnp.full((cap,), n, jnp.int32).at[
        jnp.where(present, pos, cap)].set(jnp.arange(n), mode="drop")
    out_vals = jnp.zeros((cap,), jnp.float32).at[
        jnp.where(present, pos, cap)].set(dense[:n], mode="drop")
    return out_ids, out_vals, count


def fold_runs_ref(first, vals):
    """Running per-run left fold ``s_j = (first_j ? 0 : s_{j-1}) + v_j`` of
    :func:`repro.kernels.segment_merge.fold_runs`, as a host-side numpy
    loop.  Test-only (eager numpy, not jit-able)."""
    import numpy as np
    first = np.asarray(first).reshape(-1)
    vals = np.asarray(vals, np.float32).reshape(-1)
    out = np.empty_like(vals)
    s = np.float32(0.0)
    for j in range(vals.shape[0]):
        s = (np.float32(0.0) if first[j] else s) + vals[j]
        out[j] = s
    return out

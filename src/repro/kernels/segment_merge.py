"""Segmented left-fold Pallas kernel — ``sv_merge_add``'s run reduction.

The sparse backend's merge-add (sparsevec.py) is the paper's batched hash
insert: concat → sort → sum adjacent duplicates → compact.  The sort is an
XLA native and the compaction is a scan plus a scatter; what remains is the
per-run reduction, which XLA expresses as a ``segment_sum`` scatter whose
combine order is stream order.  This kernel computes that reduction as the
running fold

    s_j = (first_j ? 0 : s_{j-1}) + v_j

over the sorted stream, so ``s`` at each run's last position is the run's
total folded ``((0 + v_1) + v_2) + …`` — the same additions in the same
order as ``segment_sum``, bit for bit.

The stream is viewed as ``[R, BLK]`` and walked in ``SUB × BLK`` SMEM
blocks on one sequential grid axis; an SMEM scalar carries the open run's
sum from one block to the next, so the stream never has to fit on chip.
The fold runs on the scalar unit: exact by construction, one element per
step.  :func:`repro.kernels.ops.segment_merge_sorted` owns the layout work
(run flags, padding, compaction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fold_runs", "SUB", "BLK"]

SUB = 8      # SMEM block rows (the (8, 128) tiling rule)
BLK = 512    # SMEM block columns: SUB·BLK stream elements per grid step


def _fold_runs_kernel(first_ref, vals_ref, out_ref, acc_ref):
    @pl.when(pl.program_id(0) == 0)
    def _reset():
        acc_ref[0] = jnp.float32(0.0)

    sub, blk = first_ref.shape

    def body(j, s):
        i, k = j // blk, j % blk
        s = jnp.where(first_ref[i, k] != 0, jnp.float32(0.0), s) + vals_ref[i, k]
        out_ref[i, k] = s
        return s

    acc_ref[0] = jax.lax.fori_loop(0, sub * blk, body, acc_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fold_runs(first: jnp.ndarray, vals: jnp.ndarray, *,
              interpret: bool) -> jnp.ndarray:
    """Running per-run left fold of a run-flagged stream.

    Args:
      first: int32[R, BLK] — 1 where a run starts (row-major stream order;
             the first element must start a run).  R is a multiple of SUB.
      vals:  f32[R, BLK]   — the values.
    Returns:
      f32[R, BLK] — ``s_j`` as in the module docstring.
    """
    rows, blk = first.shape
    assert rows % SUB == 0, first.shape
    spec = pl.BlockSpec((SUB, blk), lambda i: (i, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _fold_runs_kernel,
        out_shape=jax.ShapeDtypeStruct(vals.shape, jnp.float32),
        grid=(rows // SUB,),
        in_specs=[spec, spec],
        out_specs=spec,
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(first, vals)

"""Ordered scatter-add Pallas kernel — the batched fetchAdd, folded in order.

The paper replaces sequential updates with atomic ``fetchAdd``; XLA replaces
atomics with ``scatter-add``, whose combine order for duplicate destinations
is the update order.  This kernel computes the same left fold
``((vec[i] + v_1) + v_2) + …`` by construction, so its result is
bit-identical to ``vec.at[idx].add(vals)`` wherever that scatter folds in
update order:

  1. (wrapper, ops.py) stable-sort the contributions by destination
     (submission order kept per destination), bucket the destinations into
     groups of ``GROUP`` and note where each group's range of the sorted
     stream starts;
  2. (kernel) one grid step per (lane, group): copy the group's
     ``ROWS × 128`` slice of ``vec`` into the output block, then walk the
     group's whole range of the stream — copied from HBM into SMEM
     ``BLOCK`` elements at a time — on the scalar unit, adding each
     contribution into its destination with a masked vector select on its
     row: no matmul, no reassociation, no size bound.

Any 32-bit dtype folds in its own arithmetic (int32 sums are exact).  The
stream stays in HBM (``pl.ANY``), which the Pallas TPU lowering does not
batch, so the kernel takes a lane axis of its own and ``vmap`` folds into
it (:func:`_folder`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scatter_fold_groups", "LANES", "ROWS", "GROUP", "SUB", "CHUNK",
           "BLOCK"]

LANES = 128
ROWS = 64                # vector rows of ``vec`` per grid step
GROUP = ROWS * LANES     # destinations per grid step
SUB = 8                  # SMEM buffer rows (the (8, 128) tiling rule)
CHUNK = 1024             # SMEM buffer columns
BLOCK = SUB * CHUNK      # stream elements per copy into SMEM


def _fold_kernel(bounds_ref, dest_hbm, vals_hbm, vec_ref, out_ref,
                 dest_buf, vals_buf):
    """One (lane, destination group): out = vec, then out[d] += v in
    stream order over the group's whole stream range, copied into SMEM
    ``BLOCK`` elements at a time."""
    b, g = pl.program_id(0), pl.program_id(1)
    out_ref[...] = vec_ref[...]
    lo, hi = bounds_ref[0, g], bounds_ref[0, g + 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def add(j, carry):
        d = dest_buf[j // CHUNK, j % CHUNK]
        v = vals_buf[j // CHUNK, j % CHUNK]
        row = out_ref[pl.ds(d // LANES, 1), :]
        out_ref[pl.ds(d // LANES, 1), :] = jnp.where(lane == d % LANES,
                                                     row + v, row)
        return carry

    def block(k, carry):
        rows = pl.ds(pl.multiple_of(k * SUB, SUB), SUB)
        pltpu.sync_copy(dest_hbm.at[b, rows], dest_buf)
        pltpu.sync_copy(vals_hbm.at[b, rows], vals_buf)
        at = k * BLOCK
        return jax.lax.fori_loop(jnp.maximum(lo, at) - at,
                                 jnp.minimum(hi, at + BLOCK) - at, add, carry)

    jax.lax.fori_loop(lo // BLOCK, (hi + BLOCK - 1) // BLOCK, block,
                      jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fold_lanes(bounds, dest, vals, vec2d, *, interpret):
    lanes, groups = bounds.shape[0], bounds.shape[2] - 1
    return pl.pallas_call(
        _fold_kernel,
        out_shape=jax.ShapeDtypeStruct(vec2d.shape, vec2d.dtype),
        grid=(lanes, groups),
        in_specs=[
            pl.BlockSpec((None,) + bounds.shape[1:], lambda b, g: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((None, ROWS, LANES), lambda b, g: (b, g, 0)),
        ],
        out_specs=pl.BlockSpec((None, ROWS, LANES), lambda b, g: (b, g, 0)),
        scratch_shapes=[pltpu.SMEM((SUB, CHUNK), jnp.int32),
                        pltpu.SMEM((SUB, CHUNK), vals.dtype)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(bounds, dest, vals, vec2d)


@functools.cache
def _folder(interpret: bool):
    """``_fold_lanes`` with a vmap rule that merges the mapped axis into the
    lane axis: the HBM stream cannot take a batch axis of its own."""
    @jax.custom_batching.custom_vmap
    def fold(bounds, dest, vals, vec2d):
        return _fold_lanes(bounds, dest, vals, vec2d, interpret=interpret)

    @fold.def_vmap
    def _rule(axis_size, in_batched, *args):
        args = [x if mapped else jnp.broadcast_to(x, (axis_size,) + x.shape)
                for x, mapped in zip(args, in_batched)]
        out = fold(*(x.reshape((-1,) + x.shape[2:]) for x in args))
        return out.reshape((axis_size, -1) + out.shape[1:]), True

    return fold


def scatter_fold_groups(bounds: jnp.ndarray, dest: jnp.ndarray,
                        vals: jnp.ndarray, vec2d: jnp.ndarray, *,
                        interpret: bool) -> jnp.ndarray:
    """Fold each group's ordered contributions into its slice of ``vec``.

    Args:
      bounds: int32[1, G+1] — group ``g``'s contributions are stream
              elements ``bounds[0, g] … bounds[0, g+1]-1``.
      dest:   int32[R, CHUNK] — the stream in row-major order: each
              contribution's destination within its group, in ``[0, GROUP)``;
              R is a multiple of SUB.
      vals:   [R, CHUNK] contribution values, ``vec2d``'s dtype.
      vec2d:  [G·ROWS, LANES] — the (padded) destination vector.
    Returns:
      ``vec2d`` with every group's contributions added in stream order.
    """
    out = _folder(interpret)(bounds[None], dest[None], vals[None],
                             vec2d[None])
    return out[0]

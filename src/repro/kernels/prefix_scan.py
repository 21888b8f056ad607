"""Blocked prefix-sum Pallas kernel — the sweep cut's backbone.

Prefix sum is one of the paper's three foundational primitives (§3): the
frontier's edge offsets, the compaction ranks and the sweep cut's cut sizes
and volumes are all scans.  The kernel scans a ``[R, 128]`` view of the
array in ``ROWS × 128`` blocks on one sequential grid axis:

  * within a block, a log-step shifted add along the lanes (7 steps of
    ``pltpu.roll``) scans every row, and the same along the sublanes scans
    the row totals — Hillis–Steele, O(log) depth per block;
  * a ``[1, 128]`` VMEM scratch carries the running total from one block
    to the next (the grid axis is ``arbitrary``, i.e. sequential).

The scan keeps its input's dtype: integer scans are exact, so int32 results
equal ``jnp.cumsum`` bit for bit; float scans reassociate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_scan", "LANES", "ROWS", "BLOCK"]

LANES = 128
ROWS = 8
BLOCK = ROWS * LANES   # elements per grid step


def _scan_kernel(x_ref, y_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _reset():
        carry_ref[...] = jnp.zeros(carry_ref.shape, carry_ref.dtype)

    x = x_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    zero = jnp.zeros_like(x)
    y = x
    for s in (1, 2, 4, 8, 16, 32, 64):              # scan each row
        y = y + jnp.where(lane >= s, pltpu.roll(y, s, 1), zero)
    tot = jnp.broadcast_to(y[:, LANES - 1:], x.shape)
    z = tot
    s = 1
    while s < ROWS:                                 # scan the row totals
        z = z + jnp.where(row >= s, pltpu.roll(z, s, 0), zero)
        s *= 2
    out = y + (z - tot) + carry_ref[...]
    y_ref[...] = out
    carry_ref[...] = jnp.broadcast_to(out[ROWS - 1:, LANES - 1:],
                                      carry_ref.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_scan(x2d: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """Inclusive row-major prefix sum of ``x2d`` ([R, 128], R a multiple of
    :data:`ROWS`), dtype preserved."""
    rows = x2d.shape[0]
    assert x2d.shape[1] == LANES and rows % ROWS == 0, x2d.shape
    return pl.pallas_call(
        _scan_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(rows // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, LANES), x2d.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2d)

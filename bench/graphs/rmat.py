"""Graph500 R-MAT edge list drawn on the device from a seed.

The Graph500 generator (graph500.org specification, Kronecker/R-MAT
section): ``edge_factor · 2^scale`` edges, each edge choosing one quadrant
per bit with probabilities (a, b, c, 1 - a - b - c), then a random
permutation of the vertex ids, as ``repro.graphs.rmat`` does on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a",
                                             "b", "c"))
def edges(key, *, scale: int, edge_factor: int, a: float, b: float,
          c: float):
    """``(src, dst)`` int32 arrays of the raw edge list (self-loops and
    duplicates included)."""
    n = 1 << scale
    e = edge_factor * n

    def bit(i, carry):
        src, dst = carry
        r = jax.random.uniform(jax.random.fold_in(key, i), (e,))
        right = r > a + b                            # dst bit
        down = ((r > a) & (r <= a + b)) | (r > a + b + c)   # src bit
        return (src | (down.astype(jnp.int32) << i),
                dst | (right.astype(jnp.int32) << i))

    zero = jnp.zeros((e,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    perm = jax.random.permutation(jax.random.fold_in(key, scale),
                                  jnp.arange(n, dtype=jnp.int32))
    return perm[src], perm[dst]


def generate(key, cfg: dict):
    """``(src, dst, n)`` for the configuration ``cfg``."""
    src, dst = edges(key, scale=cfg["scale"], edge_factor=cfg["edge_factor"],
                     a=cfg["a"], b=cfg["b"], c=cfg["c"])
    return src, dst, 1 << cfg["scale"]

"""Graph500 R-MAT edge list drawn on the device from a seed, and its graph.

The Graph500 generator (graph500.org specification, Kronecker/R-MAT
section): ``edge_factor · 2^scale`` edges, each edge choosing one quadrant
per bit with probabilities (a, b, c, 1 - a - b - c), then a random
permutation of the vertex ids, as ``repro.graphs.rmat`` does on the host.

Edge ``j``'s bit ``i`` comes from element ``j`` of ``jax.random.uniform(
fold_in(key, i), (e,))``.  JAX's partitionable threefry makes that element a
function of the key and ``j`` alone, so :func:`block` draws edges
``[k·size, (k+1)·size)`` of the very stream :func:`edges` draws whole: a
graph built block by block over any number of chips is the graph one chip
builds from :func:`edges`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend.random import threefry2x32_p

from repro.graphs import GraphHandle

from bench.graphs import csr, shards

BLOCK = 1 << 24         # edges a sharded build draws at a time


def _params(cfg: dict) -> dict:
    return dict(scale=cfg["scale"], edge_factor=cfg["edge_factor"],
                a=cfg["a"], b=cfg["b"], c=cfg["c"])


def _quadrant(r, a: float, b: float, c: float):
    """(src bit, dst bit) of the quadrants the uniforms ``r`` choose."""
    right = r > a + b
    down = ((r > a) & (r <= a + b)) | (r > a + b + c)
    return down.astype(jnp.int32), right.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scale",))
def _perm(key, scale: int):
    return jax.random.permutation(jax.random.fold_in(key, scale),
                                  jnp.arange(1 << scale, dtype=jnp.int32))


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
        down, right = _quadrant(r, a, b, c)
        return src | (down << i), dst | (right << i)

    zero = jnp.zeros((e,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zero, zero))
    perm = _perm(key, scale)
    return perm[src], perm[dst]


def _uniform_slice(key, start, size: int):
    """``jax.random.uniform(key, (e,))[start:start + size]``, computed for
    that slice alone (``start`` uint32, ``e`` at most 2³²)."""
    k = jax.random.key_data(key)
    lo = lax.iota(jnp.uint32, size) + start
    bits1, bits2 = threefry2x32_p.bind(k[0], k[1], jnp.zeros_like(lo), lo)
    mantissa = ((bits1 ^ bits2) >> jnp.uint32(9)) | jnp.uint32(0x3F800000)
    return lax.bitcast_convert_type(mantissa, jnp.float32) - jnp.float32(1)


def block(args, k, *, size: int, scale: int, a: float, b: float, c: float):
    """Edges ``[k·size, (k+1)·size)`` of :func:`edges`'s stream; ``args``
    is ``(key, perm)``."""
    key, perm = args
    start = jnp.asarray(k, jnp.uint32) * jnp.uint32(size)

    def bit(i, carry):
        src, dst = carry
        r = _uniform_slice(jax.random.fold_in(key, i), start, size)
        down, right = _quadrant(r, a, b, c)
        return src | (down << i), dst | (right << i)

    zero = jnp.zeros((size,), jnp.int32)
    src, dst = lax.fori_loop(0, scale, bit, (zero, zero))
    return perm[src], perm[dst]


def generate(key, cfg: dict):
    """``(src, dst, n)`` for the configuration ``cfg``."""
    src, dst = edges(key, **_params(cfg))
    return src, dst, 1 << cfg["scale"]


def build(key, cfg: dict, devices) -> GraphHandle:
    """The configuration's graph over ``devices``: one device holds the CSR
    of :func:`edges`, several hold its rows in range shards, drawn in
    blocks of ``BLOCK`` edges."""
    p = _params(cfg)
    n, m = 1 << p["scale"], cfg["undirected_edges"]
    if len(devices) == 1:
        src, dst = edges(key, **p)
        graph = csr.build_csr(src, dst, n, m)
        jax.block_until_ready(graph.indices)
        return GraphHandle.from_csr(graph)
    e = p.pop("edge_factor") * n
    size = min(e, BLOCK)
    if e % size or e > 1 << 32:
        raise ValueError(f"{e} edges do not split into blocks of {size}")
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError("block draws need jax_threefry_partitionable")
    draw = functools.partial(block, size=size, **p)
    return shards.build(draw, (key, _perm(key, p["scale"])), e // size, n,
                        m, devices)

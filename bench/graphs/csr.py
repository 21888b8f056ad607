"""Build a symmetric CSR on the device, in ``repro.graphs.build_csr``'s order.

``repro.graphs.build_csr`` (host numpy) drops self-loops, dedupes on
``(lo, hi)``, symmetrizes as ``[lo→hi ; hi→lo]`` and sorts stably by source,
so row ``v`` holds its neighbours above ``v`` ascending, then those below
``v`` ascending.  :func:`build_csr` does the same with int32 sorts on the
device: a two-key sort on ``(lo, hi)`` dedupes, and a two-key sort on
``(src, (dst - src - 1) mod n)`` puts every row in that order at once.

The number of undirected edges kept, ``m``, is fixed by the caller: the
``m`` first unique ``(lo, hi)`` pairs in ascending order.  ``m`` and the
array shapes are static in every program compiled against the graph, so a
graph of fixed ``m`` lets every seed reuse one set of compiled programs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.graphs import CSRGraph


@functools.partial(jax.jit, static_argnames=("n",))
def count_unique(src, dst, n: int):
    """Undirected edges left after dropping self-loops and duplicates."""
    lo, hi, keep = _sorted_unique(src, dst, n)
    return jnp.sum(keep, dtype=jnp.int32)


def _sorted_unique(src, dst, n: int):
    lo = jnp.minimum(src, dst)
    hi = jnp.maximum(src, dst)
    loop = lo == hi
    lo = jnp.where(loop, n, lo)        # self-loops sort last and are dropped
    hi = jnp.where(loop, n, hi)
    lo, hi = jax.lax.sort((lo, hi), num_keys=2)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    return lo, hi, new & (lo < n)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _unique_pairs(src, dst, n: int, m: int):
    lo, hi, keep = _sorted_unique(src, dst, n)
    found = jnp.sum(keep, dtype=jnp.int32)
    idx = jnp.nonzero(keep, size=m, fill_value=0)[0]
    return lo[idx], hi[idx], found


@functools.partial(jax.jit, static_argnames=("n",))
def _rows(lo, hi, n: int):
    src = jnp.concatenate([lo, hi])
    dst = jnp.concatenate([hi, lo])
    key = dst - src - 1
    key = jnp.where(key < 0, key + n, key)
    src, key = jax.lax.sort((src, key), num_keys=2)
    dst = src + key + 1
    indices = jnp.where(dst >= n, dst - n, dst)
    indptr = jnp.searchsorted(src, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    deg = indptr[1:] - indptr[:-1]
    return indptr, indices, deg


def build_csr(src, dst, n: int, m: int) -> CSRGraph:
    """CSR of the first ``m`` unique undirected edges of ``(src, dst)``.

    Raises ``ValueError`` when the edge list has fewer than ``m`` unique
    edges, so a graph never silently comes out smaller than its
    configuration states."""
    lo, hi, found = _unique_pairs(src, dst, n, m)
    found = int(found)
    if found < m:
        raise ValueError(f"edge list has {found} unique undirected edges, "
                         f"fewer than the {m} the configuration keeps")
    indptr, indices, deg = _rows(lo, hi, n)
    return CSRGraph(indptr=indptr, indices=indices, deg=deg, n=n, m=m)

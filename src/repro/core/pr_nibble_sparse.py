"""PR-Nibble with true sparse-set state (paper-faithful memory profile).

Same algorithm as :mod:`repro.core.pr_nibble` but ``p`` and ``r`` are
:class:`SparseVec` sort-merge sparse sets instead of dense f32[n] vectors:
memory is O(cap_v) = O(|support|), independent of n — the claim that makes
the algorithms "local" in the paper.  Used to cross-check the dense backend
and to serve billion-vertex graphs where even one dense f32[n] per query is
wasteful.

Like :mod:`repro.core.pr_nibble`, the loop is decomposed into
``init / round / alive`` so the batched driver (core/batched_sparse.py) and
the serving engine (serve/cluster_engine.py) can step the *same* round
function the single-seed driver runs — that sharing is what makes their
per-seed bit-identity guarantee structural rather than aspirational.

The round takes its next frontier from ``r``'s sorted support, not from
a pack of the round's candidates (the pushed and the pushed-to vertices):
for ε > 0 every vertex at or above its threshold ``d(v)·ε`` after a round
was touched in that round.  A vertex untouched in round t keeps its
residual from round t−1; had that been above threshold, the vertex would
have been in round t's frontier, and so touched.  (After round 1 only the
seed and its neighbours hold residual; an overflow ends the run.)  So a
filter of ``r``'s ``cap_v`` slots gives the frontier the candidate pack
gives — same ids, order, count and overflow — with no sort.  The host
entry points reject ε ≤ 0 (:func:`check_sparse_eps`), where a zero
residual left by a push would pass the filter.

Shape/dtype contracts (``n`` = graph.n; sentinel id is ``n``):
  * state ``p``, ``r`` — :class:`SparseVec` of capacity ``cap_v``:
    ``ids`` int32[cap_v] sorted/sentinel-padded, ``vals`` f32[cap_v],
    ``count`` int32 scalar, ``overflow`` bool scalar.
  * ``frontier`` — :class:`Frontier` of capacity ``cap_f``.
  * results carry int32 scalar ``iterations``/``pushes`` and a bool
    ``overflow`` that ORs every capacity violation (frontier, edge
    workspace, or SparseVec) seen on the way.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.csr import CSRGraph
from .frontier import Frontier, compact_sorted, expand, singleton
from .sparsevec import (SparseVec, sv_empty, sv_from_pairs, sv_lookup,
                        sv_merge_add, sv_update_existing)

__all__ = ["PRNibbleSparseResult", "PRNibbleSparseState", "pr_nibble_sparse",
           "pr_nibble_sparse_fixedcap", "pr_nibble_sparse_init",
           "pr_nibble_sparse_round", "pr_nibble_sparse_alive",
           "check_sparse_eps"]


class PRNibbleSparseResult(NamedTuple):
    p: SparseVec
    r: SparseVec
    iterations: jnp.ndarray
    pushes: jnp.ndarray
    overflow: jnp.ndarray


class PRNibbleSparseState(NamedTuple):
    """Loop carry of one sparse PR-Nibble run — exposed so the batched and
    streaming drivers can step the same rounds (cf. ``PRNibbleState``)."""
    p: SparseVec
    r: SparseVec
    frontier: Frontier
    t: jnp.ndarray
    pushes: jnp.ndarray
    edge_work: jnp.ndarray   # int32 — Σ expanded edges (eb.total) per round
    overflow: jnp.ndarray


def check_sparse_eps(eps) -> None:
    """Reject an ε that is not > 0 (NaN included), scalar or per seed.

    The round's next frontier is ``r``'s support filtered by
    ``r[v] >= d(v)·ε``; with ε ≤ 0 a zero-valued entry left behind by a push
    would pass it, and the run would never end before ``max_iters``."""
    e = np.asarray(eps, dtype=np.float64)
    bad = e[~(e > 0)]
    if bad.size:
        raise ValueError(
            f"sparse PR-Nibble needs eps > 0 (got {float(bad.flat[0])!r})")


def pr_nibble_sparse_init(x, n: int, cap_f: int, cap_v: int) -> PRNibbleSparseState:
    """Initial state: unit residual on the seed, seed frontier, empty p.

    ``x`` is an int32 seed id (scalar or 0-d array); the state's SparseVecs
    have capacity ``cap_v`` and the frontier capacity ``cap_f``.
    """
    r0 = sv_from_pairs(jnp.full((1,), jnp.asarray(x, jnp.int32)),
                       jnp.ones((1,), jnp.float32),
                       jnp.ones((1,), bool), cap_v, n)
    return PRNibbleSparseState(p=sv_empty(cap_v, n), r=r0,
                               frontier=singleton(x, n, cap_f),
                               t=jnp.asarray(0, jnp.int32),
                               pushes=jnp.asarray(0, jnp.int32),
                               edge_work=jnp.asarray(0, jnp.int32),
                               overflow=jnp.asarray(False))


def pr_nibble_sparse_alive(s: PRNibbleSparseState,
                           max_iters: int = 10_000) -> jnp.ndarray:
    """True while the run still has above-threshold residual to push."""
    return (s.frontier.count > 0) & (~s.overflow) & (s.t < max_iters)


def pr_nibble_sparse_round(graph: CSRGraph, s: PRNibbleSparseState, eps, alpha,
                           optimized: bool, cap_e: int,
                           backend: str = "xla") -> PRNibbleSparseState:
    """One synchronous push round over the sparse state (Figures 3–4).

    ``backend`` routes both ``sv_merge_add`` reductions (the round's hot
    loop) plus the expand/pack scans through :mod:`repro.core.ops` —
    ``"pallas"`` runs them on the segment-merge kernel with bit-identical
    results where XLA folds in update order (guarantee #6)."""
    n = graph.n
    deg = graph.deg
    f = s.frontier
    fvalid = f.valid()
    fids = jnp.where(fvalid, f.ids, n)
    safe = jnp.minimum(fids, n - 1)
    rf = jnp.where(fvalid, sv_lookup(s.r, fids, n), 0.0)
    dv = jnp.maximum(deg[safe], 1)

    if optimized:
        p_gain = (2.0 * alpha / (1.0 + alpha)) * rf
        r_self = jnp.zeros_like(rf)
        share = ((1.0 - alpha) / (1.0 + alpha)) * rf / dv
    else:
        p_gain = alpha * rf
        r_self = (1.0 - alpha) * rf / 2.0
        share = (1.0 - alpha) * rf / (2.0 * dv)

    p_new = sv_merge_add(s.p, fids, p_gain, fvalid, n, backend=backend)
    r_new = sv_update_existing(s.r, fids, r_self, fvalid)
    eb = expand(graph, f, cap_e, backend=backend)
    r_new = sv_merge_add(r_new, eb.dst, share[eb.slot], eb.valid, n,
                         backend=backend)

    # next frontier: r_new's own support, filtered (module docstring)
    with jax.named_scope("frontier"):
        rdeg = deg[jnp.minimum(r_new.ids, n - 1)]
        keep = r_new.valid() & (rdeg > 0) & (r_new.vals >= rdeg * eps)
        nf = compact_sorted(r_new.ids, keep, n, f.cap, backend=backend)

    return PRNibbleSparseState(p=p_new, r=r_new, frontier=nf, t=s.t + 1,
                               pushes=s.pushes + f.count,
                               edge_work=s.edge_work + eb.total,
                               overflow=(s.overflow | nf.overflow |
                                         eb.overflow | p_new.overflow |
                                         r_new.overflow))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("optimized", "cap_f", "cap_e", "cap_v",
                                    "max_iters", "backend"))
def pr_nibble_sparse_fixedcap(graph: CSRGraph, x, eps, alpha,
                              optimized: bool, cap_f: int, cap_e: int,
                              cap_v: int, max_iters: int = 10_000, *,
                              backend: str = "xla") -> PRNibbleSparseResult:
    def cond(s: PRNibbleSparseState):
        return pr_nibble_sparse_alive(s, max_iters)

    def body(s: PRNibbleSparseState) -> PRNibbleSparseState:
        return pr_nibble_sparse_round(graph, s, eps, alpha, optimized, cap_e,
                                      backend)

    s = jax.lax.while_loop(cond, body,
                           pr_nibble_sparse_init(x, graph.n, cap_f, cap_v))
    return PRNibbleSparseResult(p=s.p, r=s.r, iterations=s.t, pushes=s.pushes,
                                overflow=s.overflow)


def pr_nibble_sparse(graph: CSRGraph, x, eps: float = 1e-7, alpha: float = 0.01,
                     optimized: bool = True, cap_f: int = 1 << 10,
                     cap_e: int = 1 << 14, cap_v: int = 1 << 12,
                     max_cap_e: int = 1 << 26,
                     backend: str = "xla") -> PRNibbleSparseResult:
    """Bucketed driver: retry with doubled capacities on overflow.

    The doubling schedule (cap_f, cap_v clamped to n+1; cap_e unclamped up to
    ``max_cap_e``) is shared verbatim by ``batched_pr_nibble_sparse`` and the
    serving engine's bucket-promotion ladder, so all three paths dispatch the
    same static shapes and return bit-identical per-seed results.
    """
    check_sparse_eps(eps)
    while True:
        out = pr_nibble_sparse_fixedcap(graph, x, eps, alpha, optimized,
                                        cap_f, cap_e, cap_v, backend=backend)
        if not bool(out.overflow) or cap_e >= max_cap_e:
            return out
        cap_f = min(cap_f * 2, graph.n + 1)
        cap_e *= 2
        cap_v = min(cap_v * 2, graph.n + 1)

"""Parallel PR-Nibble (paper §4.3, Figures 3–4) — approximate personalized
PageRank by synchronous parallel push.

Each round pushes from *every* vertex with ``r[v] ≥ d(v)·ε`` simultaneously,
reading the residual ``r`` frozen at the start of the round and accumulating
into the double buffer ``r'`` (the paper's race-free design; the asynchronous
single-buffer variant leaks mass and is explicitly rejected in §4.3).

Two update rules:
  * ``original``  (Fig 3):  p[v] += α·r[v];           r'[v] = (1−α)·r[v]/2;
                            r'[w] += (1−α)·r[v]/(2d(v))
  * ``optimized`` (Fig 4):  p[v] += 2α/(1+α)·r[v];    r'[v] = 0;
                            r'[w] += (1−α)/(1+α)·r[v]/d(v)
    (optimal coordinate-descent step size — same conductance guarantee,
    1.4–6.4× less work in the paper's Fig 2.)

Work O(1/(αε)) for either rule (Theorem 3) — independent of round count.

Beyond the paper: a ``beta`` knob selects only the top β-fraction of
above-threshold vertices by r[v]/d(v) each round (the paper's work/parallelism
trade-off variant, reported but not detailed there).

Backends:
  * dense  — state vectors are dense f32[n]; per-round *work* is still
             O(vol(frontier)) (all gathers/scatters are frontier-sized).
  * sparse — `SparseVec` sort-merge sparse sets (see sparsevec.py): true
             O(|support|) memory, the faithful analogue of the paper's
             concurrent hash table.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from .frontier import (Frontier, expand, pack_unique, singleton, seed_set,
                       scatter_add_dense, scatter_set_dense, one_hot_f32)

__all__ = ["PRNibbleResult", "PRNibbleState", "pr_nibble", "pr_nibble_fixedcap",
           "pr_nibble_init", "pr_nibble_round", "pr_nibble_alive", "MAX_ITERS"]

# Round budget shared by every driver that must stay bit-identical to this
# one (core/batched.py, serve/cluster_engine.py import it).
MAX_ITERS = 10_000


class PRNibbleResult(NamedTuple):
    p: jnp.ndarray           # f32[n]
    r: jnp.ndarray           # f32[n] — final residual
    iterations: jnp.ndarray  # int32
    pushes: jnp.ndarray      # int32  (Table 1 counter)
    edge_work: jnp.ndarray   # int32
    overflow: jnp.ndarray    # bool


class PRNibbleState(NamedTuple):
    """Loop carry of one PR-Nibble run — exposed so batched/streaming drivers
    (core/batched.py, serve/cluster_engine.py) can step the same rounds."""
    p: jnp.ndarray
    r: jnp.ndarray
    frontier: Frontier
    t: jnp.ndarray
    pushes: jnp.ndarray
    edge_work: jnp.ndarray
    overflow: jnp.ndarray


def pr_nibble_init(x, n: int, cap_f: int) -> PRNibbleState:
    """Initial state: unit residual mass on the seed (or 1/k per seed-set
    vertex, paper footnote 3) and the seed frontier."""
    if isinstance(x, tuple):
        seeds, count = x
        seeds = jnp.asarray(seeds, jnp.int32)
        valid = jnp.arange(seeds.shape[0]) < count
        r0 = scatter_add_dense(jnp.zeros((n,), jnp.float32), seeds,
                               jnp.full(seeds.shape, 1.0 / count, jnp.float32),
                               valid)
        front0 = seed_set(seeds, count, n, cap_f)
    else:
        r0 = one_hot_f32(x, n)
        front0 = singleton(x, n, cap_f)
    return PRNibbleState(p=jnp.zeros((n,), jnp.float32), r=r0,
                         frontier=front0,
                         t=jnp.asarray(0, jnp.int32),
                         pushes=jnp.asarray(0, jnp.int32),
                         edge_work=jnp.asarray(0, jnp.int32),
                         overflow=jnp.asarray(False))


def pr_nibble_alive(s: PRNibbleState, max_iters: int = MAX_ITERS) -> jnp.ndarray:
    """True while the run still has above-threshold residual to push."""
    return (s.frontier.count > 0) & (~s.overflow) & (s.t < max_iters)


def pr_nibble_round(graph: CSRGraph, s: PRNibbleState, eps, alpha,
                    optimized: bool, cap_e: int,
                    beta: float = 1.0, backend: str = "xla") -> PRNibbleState:
    """One synchronous push round (the while-loop body of Figures 3–4).

    ``backend`` selects the kernel backend for every scatter/scan in the
    round (see :mod:`repro.core.ops`); results are bit-identical across
    backends where XLA folds in update order (guarantee #6)."""
    n = graph.n
    deg = graph.deg
    f = s.frontier
    fvalid = f.valid()
    fids = jnp.where(fvalid, f.ids, n)
    safe = jnp.minimum(fids, n - 1)
    all_fids, all_fvalid = fids, fvalid  # full frontier (pre-β) for re-filter

    if beta < 1.0:
        # β-selection: push only the top β-fraction by r/d (paper's
        # work-vs-parallelism trade-off variant)
        r_over_d = jnp.where(fvalid, s.r[safe] / jnp.maximum(deg[safe], 1),
                             -jnp.inf)
        k = jnp.maximum(jnp.ceil(beta * f.count), 1.0).astype(jnp.int32)
        kth = -jnp.sort(-r_over_d)[jnp.minimum(k - 1, f.cap - 1)]
        sel = fvalid & (r_over_d >= kth)
        # re-pack: Frontier validity is prefix-based, so the selected ids
        # must be compacted to the front
        f = pack_unique(fids, sel, n, f.cap, backend=backend)
        fvalid = f.valid()
        fids = jnp.where(fvalid, f.ids, n)
        safe = jnp.minimum(fids, n - 1)

    rf = jnp.where(fvalid, s.r[safe], 0.0)
    dv = jnp.maximum(deg[safe], 1)

    if optimized:
        p_gain = (2.0 * alpha / (1.0 + alpha)) * rf
        r_self = jnp.zeros_like(rf)
        share = ((1.0 - alpha) / (1.0 + alpha)) * rf / dv
    else:
        p_gain = alpha * rf
        r_self = (1.0 - alpha) * rf / 2.0
        share = (1.0 - alpha) * rf / (2.0 * dv)

    p_new = scatter_add_dense(s.p, fids, p_gain, fvalid, backend=backend)
    # r' starts as r with frontier entries replaced (double buffer)
    r_new = scatter_set_dense(s.r, fids, r_self, fvalid)

    eb = expand(graph, f, cap_e, backend=backend)
    contrib = share[eb.slot]
    r_new = scatter_add_dense(r_new, eb.dst, contrib, eb.valid,
                              backend=backend)

    with jax.named_scope("frontier"):
        cands = jnp.concatenate([all_fids, eb.dst])
        cvalid = jnp.concatenate([all_fvalid, eb.valid])
        csafe = jnp.minimum(cands, n - 1)
        keep = cvalid & (deg[csafe] > 0) & (r_new[csafe] >= deg[csafe] * eps)
        nf = pack_unique(cands, keep, n, s.frontier.cap, backend=backend)

    return PRNibbleState(p=p_new, r=r_new, frontier=nf, t=s.t + 1,
                         pushes=s.pushes + f.count,
                         edge_work=s.edge_work + eb.total,
                         overflow=s.overflow | nf.overflow | eb.overflow)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("optimized", "cap_f", "cap_e",
                                    "max_iters", "beta", "backend"))
def pr_nibble_fixedcap(graph: CSRGraph, x, eps, alpha,
                       optimized: bool, cap_f: int, cap_e: int,
                       max_iters: int = MAX_ITERS, beta: float = 1.0, *,
                       backend: str = "xla") -> PRNibbleResult:
    def cond(s: PRNibbleState):
        return pr_nibble_alive(s, max_iters)

    def body(s: PRNibbleState) -> PRNibbleState:
        return pr_nibble_round(graph, s, eps, alpha, optimized, cap_e, beta,
                               backend)

    s = jax.lax.while_loop(cond, body, pr_nibble_init(x, graph.n, cap_f))
    return PRNibbleResult(p=s.p, r=s.r, iterations=s.t, pushes=s.pushes,
                          edge_work=s.edge_work, overflow=s.overflow)


def pr_nibble(graph: CSRGraph, x, eps: float = 1e-7, alpha: float = 0.01,
              optimized: bool = True, cap_f: int = 1 << 12, cap_e: int = 1 << 16,
              max_cap_e: int = 1 << 26, beta: float = 1.0,
              backend: str = "xla") -> PRNibbleResult:
    """Bucketed driver: retry with doubled capacities on overflow."""
    while True:
        out = pr_nibble_fixedcap(graph, x, eps, alpha, optimized, cap_f, cap_e,
                                 beta=beta, backend=backend)
        if not bool(out.overflow) or cap_e >= max_cap_e:
            return out
        cap_f = min(cap_f * 2, graph.n + 1)
        cap_e = cap_e * 2

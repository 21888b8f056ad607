"""Parallel deterministic heat-kernel PageRank (paper §4.4, Figure 5).

Kloster–Gleich hk-relax: approximate h = e⁻ᵗ Σₖ tᵏ/k! · Pᵏ s via its degree-N
Taylor polynomial, pushing residual mass level by level.  The paper's insight:
all queue entries with the same Taylor index j can be processed in parallel
(they only write level j+1), so the rounds of the parallel algorithm are the
Taylor levels and the output is *identical* to the sequential algorithm.

ψ coefficients: ψ_N = 1, ψ_k = 1 + t·ψ_{k+1}/(k+1)  (O(N) instead of the
naive O(N²); still matches Theorem 4's bound).  Threshold (Fig 5 /
Kloster–Gleich):  r[v] ≥ eᵗ·ε·d(v) / (2N·ψ_{j+1}(t)).

Work O(N² + N·eᵗ/ε), depth O(N·t·log(1/ε))  (Theorem 4).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from .frontier import (Frontier, expand, pack_unique, singleton,
                       scatter_add_dense, one_hot_f32)

__all__ = ["HKPRResult", "HKPRState", "hk_pr", "hk_pr_fixedcap", "psis",
           "hk_pr_init", "hk_pr_round", "hk_pr_alive"]


def psis(N: int, t: float) -> np.ndarray:
    psi = np.ones(N + 1, dtype=np.float64)
    for k in range(N - 1, -1, -1):
        psi[k] = 1.0 + t * psi[k + 1] / (k + 1)
    return psi


class HKPRResult(NamedTuple):
    p: jnp.ndarray
    iterations: jnp.ndarray
    pushes: jnp.ndarray
    edge_work: jnp.ndarray
    overflow: jnp.ndarray


class HKPRState(NamedTuple):
    """Loop carry of one hk-relax run — exposed so batched/streaming drivers
    (core/batched.py, serve/cluster_engine.py) can step the same rounds."""
    p: jnp.ndarray
    r: jnp.ndarray
    frontier: Frontier
    j: jnp.ndarray
    pushes: jnp.ndarray
    edge_work: jnp.ndarray
    done: jnp.ndarray
    overflow: jnp.ndarray


def hk_pr_init(x, n: int, cap_f: int) -> HKPRState:
    r0 = one_hot_f32(x, n)
    return HKPRState(p=jnp.zeros((n,), jnp.float32), r=r0,
                     frontier=singleton(x, n, cap_f),
                     j=jnp.asarray(0, jnp.int32),
                     pushes=jnp.asarray(0, jnp.int32),
                     edge_work=jnp.asarray(0, jnp.int32),
                     done=jnp.asarray(False),
                     overflow=jnp.asarray(False))


def hk_pr_alive(s: HKPRState) -> jnp.ndarray:
    return (~s.done) & (~s.overflow) & (s.frontier.count > 0)


def hk_pr_round(graph: CSRGraph, s: HKPRState, N: int, eps, t: float,
                cap_e: int, backend: str = "xla") -> HKPRState:
    """One Taylor level (the while-loop body of Figure 5).  ``N`` and ``t``
    are trace-time constants: the ψ table is precomputed host-side in
    float64.  ``backend`` routes the scatters/scans (repro.core.ops)."""
    n = graph.n
    deg = graph.deg
    psi_table = jnp.asarray(psis(N, float(t)), jnp.float32)
    scale = jnp.exp(jnp.asarray(t, jnp.float32))

    f = s.frontier
    fvalid = f.valid()
    fids = jnp.where(fvalid, f.ids, n)
    safe = jnp.minimum(fids, n - 1)
    rf = jnp.where(fvalid, s.r[safe], 0.0)
    dv = jnp.maximum(deg[safe], 1)

    # VERTEXMAP (UpdateSelf): p[v] += r[v]
    p_new = scatter_add_dense(s.p, fids, rf, fvalid, backend=backend)

    eb = expand(graph, f, cap_e, backend=backend)
    last = s.j + 1 >= N

    # last round (UpdateNghLast): p[w] += r[v]/d(v), then stop
    contrib_last = rf[eb.slot] / dv[eb.slot]
    p_last = scatter_add_dense(p_new, eb.dst, contrib_last, eb.valid,
                               backend=backend)

    # normal round (UpdateNgh): r'[w] += t·r[v]/((j+1)·d(v)); fresh r'
    contrib = (t * rf[eb.slot]) / ((s.j + 1.0) * dv[eb.slot])
    r_next = jnp.zeros_like(s.r)
    r_next = scatter_add_dense(r_next, eb.dst, contrib, eb.valid,
                               backend=backend)

    # frontier for level j+1: r'[v] ≥ eᵗ ε d(v) / (2N ψ_{j+1})
    with jax.named_scope("frontier"):
        thresh_coef = scale * eps / (2.0 * N *
                                     psi_table[jnp.minimum(s.j + 1, N)])
        cands = eb.dst
        csafe = jnp.minimum(cands, n - 1)
        keep = eb.valid & (deg[csafe] > 0) & \
            (r_next[csafe] >= deg[csafe] * thresh_coef)
        nf = pack_unique(cands, keep, n, s.frontier.cap, backend=backend)

    return HKPRState(
        p=jnp.where(last, p_last, p_new),
        r=jnp.where(last, s.r, r_next),
        frontier=nf,
        j=s.j + 1,
        pushes=s.pushes + f.count,
        edge_work=s.edge_work + eb.total,
        done=last,
        overflow=s.overflow | eb.overflow | (nf.overflow & ~last),
    )


@functools.partial(jax.jit, static_argnums=(2, 4, 5, 6),
                   static_argnames=("N", "t", "cap_f", "cap_e", "backend"))
def hk_pr_fixedcap(graph: CSRGraph, x, N: int, eps, t: float,
                   cap_f: int, cap_e: int, *,
                   backend: str = "xla") -> HKPRResult:
    def cond(s: HKPRState):
        return hk_pr_alive(s)

    def body(s: HKPRState) -> HKPRState:
        return hk_pr_round(graph, s, N, eps, t, cap_e, backend)

    s = jax.lax.while_loop(cond, body, hk_pr_init(x, graph.n, cap_f))
    return HKPRResult(p=s.p, iterations=s.j, pushes=s.pushes,
                      edge_work=s.edge_work, overflow=s.overflow)


def hk_pr(graph: CSRGraph, x, N: int = 20, eps: float = 1e-7, t: float = 10.0,
          cap_f: int = 1 << 12, cap_e: int = 1 << 16,
          max_cap_e: int = 1 << 26, backend: str = "xla") -> HKPRResult:
    """Bucketed driver: retry with doubled capacities on overflow."""
    while True:
        out = hk_pr_fixedcap(graph, x, N, eps, t, cap_f, cap_e,
                             backend=backend)
        if not bool(out.overflow) or cap_e >= max_cap_e:
            return out
        cap_f = min(cap_f * 2, graph.n + 1)
        cap_e = cap_e * 2

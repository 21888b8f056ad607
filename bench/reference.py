"""Plain numpy reference of what the service answers, independent of it.

The service answers a request with a cluster and its conductance, found by
a sweep cut over a PR-Nibble or HK-PR diffusion from the seed (the paper,
§4.1, §4.3-4.4).  This module computes the same thing from the paper's
definitions with nothing of the program: the synchronous parallel rounds
(every vertex above its threshold pushes at once, reading the residual as
it stood when the round began), then the sweep over ``p[v]/d(v)``.  It runs
in any numpy float type: float64 is the reference, and a lower precision
put in the program's place is the control that the comparison has to
reject.

One :class:`Workspace` holds dense scratch vectors of the graph's size, so a
diffusion costs time in proportion to the vertices it touches, never to n.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import ml_dtypes
import numpy as np

DTYPES = {"float64": np.float64, "bfloat16": ml_dtypes.bfloat16}
MAX_ITERS = 10_000          # round budget of a PR-Nibble run


class HostGraph(NamedTuple):
    indptr: np.ndarray      # int64[n+1]
    indices: np.ndarray     # int32[2m]
    deg: np.ndarray         # int64[n]
    n: int
    m: int

    @classmethod
    def of(cls, graph) -> "HostGraph":
        """Host copy of a CSR graph's arrays."""
        indptr = np.asarray(graph.indptr).astype(np.int64)
        return cls(indptr, np.asarray(graph.indices), np.diff(indptr),
                   int(graph.n), int(graph.m))


class Answer(NamedTuple):
    conductance: float
    cluster: np.ndarray     # member ids, best sweep prefix
    support: int            # vertices with p > 0
    pushes: int


def expand(g: HostGraph, f: np.ndarray):
    """(slot, neighbour) for every edge leaving the vertices ``f``."""
    counts = g.deg[f]
    slot = np.repeat(np.arange(f.size), counts)
    first = np.repeat(g.indptr[f] - (np.cumsum(counts) - counts), counts)
    return slot, g.indices[first + np.arange(slot.size)]


class Workspace:
    """Dense scratch over the vertices of one graph, in one float type."""

    def __init__(self, g: HostGraph, dtype="float64"):
        self.g = g
        self.dt = DTYPES[dtype]
        self.p = np.zeros(g.n, self.dt)
        self.r = np.zeros(g.n, self.dt)
        self.r2 = np.zeros(g.n, self.dt)
        self.rank = np.full(g.n, -1, np.int64)
        self.touched = []

    def _reset(self):
        t = np.unique(np.concatenate(self.touched)) if self.touched else []
        self.p[t] = 0
        self.r[t] = 0
        self.r2[t] = 0
        self.touched = []

    # -- diffusions ----------------------------------------------------------

    def pr_nibble(self, seed: int, alpha: float, eps: float):
        """Optimized-rule PR-Nibble (Fig 4), synchronous rounds from
        ``seed``.  Returns (support ids, pushes)."""
        g, dt, p, r = self.g, self.dt, self.p, self.r
        c_p, c_s = dt(2 * alpha / (1 + alpha)), dt((1 - alpha) / (1 + alpha))
        f = np.array([seed], np.int64)
        r[seed] = 1
        self.touched.append(f)
        pushes = t = 0
        while f.size and t < MAX_ITERS:
            rf = r[f].copy()
            p[f] += c_p * rf
            r[f] = 0
            share = c_s * rf / g.deg[f].astype(dt)
            slot, nbr = expand(g, f)
            np.add.at(r, nbr, share[slot])
            self.touched.append(nbr)
            cands = np.unique(np.concatenate([f, nbr]))
            cands = cands[g.deg[cands] > 0]
            pushes += f.size
            t += 1
            f = cands[r[cands] >= g.deg[cands].astype(dt) * dt(eps)]
        return pushes

    def hk_pr(self, seed: int, N: int, t: float, eps: float):
        """Heat-kernel push (Fig 5), one Taylor level per round.  Returns
        pushes."""
        g, dt, p = self.g, self.dt, self.p
        psi = np.ones(N + 1)
        for k in range(N - 1, -1, -1):
            psi[k] = 1.0 + t * psi[k + 1] / (k + 1)
        r, r_next = self.r, self.r2
        f = np.array([seed], np.int64)
        r[seed] = 1
        written = f                     # entries of r set at this level
        self.touched.append(f)
        pushes = 0
        for j in range(N):
            rf = r[f].copy()
            d = np.maximum(g.deg[f], 1).astype(dt)
            p[f] += rf
            slot, nbr = expand(g, f)
            self.touched.append(nbr)
            pushes += f.size
            if j + 1 >= N:
                np.add.at(p, nbr, (rf / d)[slot])
                break
            np.add.at(r_next, nbr, (dt(t) * rf / (dt(j + 1) * d))[slot])
            coef = dt(math.exp(t) * eps / (2 * N * psi[j + 1]))
            cands = np.unique(nbr)
            cands = cands[g.deg[cands] > 0]
            f = cands[r_next[cands] >= g.deg[cands].astype(dt) * coef]
            r[written] = 0              # each level starts from a fresh r'
            written = nbr
            r, r_next = r_next, r
            if not f.size:
                break
        return pushes

    # -- sweep ---------------------------------------------------------------

    def sweep(self) -> Answer:
        """Best sweep prefix over ``p[v]/d(v)`` of the last diffusion."""
        g, dt = self.g, self.dt
        ids = np.unique(np.concatenate(self.touched))
        ids = ids[(self.p[ids] > 0) & (g.deg[ids] > 0)]
        q = (self.p[ids] / g.deg[ids].astype(dt)).astype(np.float64)
        order = ids[np.lexsort((ids, -q))]
        k = order.size
        self.rank[order] = np.arange(k)
        slot, nbr = expand(g, order)
        r_dst = self.rank[nbr]
        r_dst = np.where(r_dst < 0, k, r_dst)
        go = slot < r_dst
        diff = np.zeros(k + 2, np.int64)
        np.add.at(diff, slot[go] + 1, 1)
        np.add.at(diff, r_dst[go] + 1, -1)
        cut = np.cumsum(diff)[1:k + 1]
        vol = np.cumsum(g.deg[order])
        denom = np.minimum(vol, 2 * g.m - vol)
        with np.errstate(divide="ignore", invalid="ignore"):
            phi = np.where(denom > 0, cut.astype(dt) / denom.astype(dt),
                           dt(np.inf))
        self.rank[order] = -1
        best = int(np.argmin(phi))
        return Answer(float(phi[best]), order[:best + 1], k, 0)

    def answer(self, req) -> Answer:
        """The reference answer to a ``ClusterRequest``-like request."""
        try:
            if req.method == "hk_pr":
                pushes = self.hk_pr(req.seed, req.N, req.t, req.eps)
            else:
                pushes = self.pr_nibble(req.seed, req.alpha, req.eps)
            return self.sweep()._replace(pushes=pushes)
        finally:
            self._reset()


def conductance_of(g: HostGraph, members: np.ndarray):
    """(φ, volume) of a vertex set, exactly, or None where ``members`` is
    no set of distinct vertices of ``g``."""
    members = np.asarray(members, np.int64)
    if (members.size == 0 or members.min() < 0 or members.max() >= g.n
            or np.unique(members).size != members.size):
        return None
    inside = np.zeros(g.n, bool)
    inside[members] = True
    _, nbr = expand(g, members)
    vol = int(g.deg[members].sum())
    cut = int(np.count_nonzero(~inside[nbr]))
    denom = min(vol, 2 * g.m - vol)
    return (cut / denom if denom > 0 else math.inf), vol

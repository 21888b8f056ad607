"""Network Community Profile driver (paper §5, Figure 10).

NCP(s) = best conductance over all found clusters of size s.  The paper
generates it by running PR-Nibble from 10⁵ random seeds over a grid of
(α, ε) and sweeping each output — "a straightforward way to use parallelism
is to run many local graph computations independently in parallel".

The outer loop rides the batched multi-seed subsystem
(:mod:`repro.core.batched`): each batch of seeds runs as one XLA program
through the fused diffusion+sweep kernel, and seeds whose frontier
overflowed the capacity bucket are retried at the next power-of-two bucket
instead of being dropped — every seed contributes to the profile.  Batches
are sharded over the `data` mesh axis by the distributed launcher; this is
the multi-pod embodiment of the paper's interactive-analytics workload.

``backend="sparse"`` swaps in the memory-bounded fused kernel from
:mod:`repro.core.batched_sparse` — same profile semantics, per-lane state
O(cap_v) instead of O(n).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax.numpy as jnp

from repro.graphs.csr import CSRGraph
from repro.graphs.handle import as_handle
from . import ops as core_ops
from .batched import batched_cluster, batched_cluster_fixedcap
from .batched_dist import batched_cluster_dist
from .batched_sparse import batched_cluster_sparse

__all__ = ["NCPResult", "ncp_batch", "ncp"]


class NCPResult(NamedTuple):
    sizes: np.ndarray         # int — cluster size grid (1..max)
    best_conductance: np.ndarray  # f32 per size (inf where none found)
    num_runs: int


def ncp_batch(graph: CSRGraph, seeds: jnp.ndarray, params: jnp.ndarray,
              cap_f: int, cap_e: int, cap_n: int, sweep_cap_e: int):
    """One vmapped batch: seeds[i] with (eps, alpha) = params[i].

    Kept for API compatibility; delegates to the fused batched kernel.
    Returns per-run (conductances[cap_n], support, overflow) — the full
    sweep curve so every prefix feeds the NCP, not just the argmin.
    """
    out = batched_cluster_fixedcap(graph, seeds, params[:, 0], params[:, 1],
                                   True, cap_f, cap_e, min(cap_n, graph.n),
                                   sweep_cap_e)
    return out.conductance, out.support, out.overflow


def ncp(graph, num_seeds: int = 256,
        alphas=(0.1, 0.01), epss=(1e-5, 1e-6, 1e-7),
        batch: int = 64, seed: int = 0,
        cap_f: int = 1 << 12, cap_e: int = 1 << 16,
        cap_n: int = 1 << 12, sweep_cap_e: int = 1 << 18,
        backend: str = "dense", cap_v: int = 1 << 12,
        ops_backend: str = "xla", mesh=None,
        dist_axis: str = "data") -> NCPResult:
    """Host driver: grid of (seed, α, ε) runs through the batched engine
    (per-seed overflow retry included).  ``graph`` is any graph-like
    (``CSRGraph`` or :class:`~repro.graphs.handle.GraphHandle`).

    ``backend="sparse"`` routes every batch through the fused sparse path
    (:func:`repro.core.batched_sparse.batched_cluster_sparse`): per-lane
    memory O(cap_v) instead of O(n), sweep curves on the
    ``min(cap_n, cap_v)`` grid — the profile a billion-vertex NCP must use.

    ``backend="dist"`` shards every batch over the handle's mesh
    (:func:`repro.core.batched_dist.batched_cluster_dist`) — the multi-host
    NCP sweep.  Per-seed diffusions are bit-identical to the dense path, so
    the profile is too.

    ``ops_backend`` ("xla" | "pallas" | "auto") is orthogonal to the lane
    choice: it selects the kernel backend every scatter/merge/scan inside
    either path dispatches through (:mod:`repro.core.ops`); profiles are
    bit-identical across ops backends where XLA folds in update order.
    """
    if backend not in ("dense", "sparse", "dist"):
        raise ValueError(f"unknown backend: {backend!r}")
    handle = as_handle(graph, mesh=mesh, axis=dist_axis)
    ops_backend = core_ops.resolve(ops_backend)
    rng = np.random.default_rng(seed)
    deg = core_ops.graph_degrees(handle)
    nonzero = np.flatnonzero(deg > 0)
    seeds = rng.choice(nonzero, size=num_seeds, replace=True).astype(np.int32)
    grid = [(e, a) for a in alphas for e in epss]

    n = handle.n
    cap_n = min(cap_n, n)         # sweep clamps its prefix cap to n
    if backend == "sparse":
        cap_n = min(cap_n, cap_v)  # sparse curves live on the cap_v grid
    best = np.full((cap_n,), np.inf, dtype=np.float32)
    runs = 0
    for (eps, alpha) in grid:
        for lo in range(0, num_seeds, batch):
            sb = seeds[lo: lo + batch]
            if sb.shape[0] < batch:  # pad final batch
                sb = np.concatenate([sb, np.repeat(sb[:1], batch - sb.shape[0])])
            if backend == "sparse":
                out = batched_cluster_sparse(handle.local(), sb, eps, alpha,
                                             cap_f=cap_f, cap_e=cap_e,
                                             cap_v=cap_v,
                                             sweep_cap_e=sweep_cap_e,
                                             backend=ops_backend)
            elif backend == "dist":
                out = batched_cluster_dist(handle, sb, eps, alpha,
                                           cap_f=cap_f, cap_e=cap_e,
                                           cap_n=cap_n,
                                           sweep_cap_e=sweep_cap_e,
                                           backend=ops_backend)
            else:
                out = batched_cluster(handle.local(), sb, eps, alpha,
                                      cap_f=cap_f, cap_e=cap_e, cap_n=cap_n,
                                      sweep_cap_e=sweep_cap_e,
                                      backend=ops_backend)
            ok = ~out.overflow
            curves = np.where(ok[:, None], out.conductance[:, :cap_n], np.inf)
            best = np.minimum(best, curves.min(axis=0))
            runs += int(ok.sum())
    sizes = np.arange(1, cap_n + 1)
    return NCPResult(sizes=sizes, best_conductance=best, num_runs=runs)

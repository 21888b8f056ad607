"""The paper's contribution: parallel local graph clustering.

Four diffusion engines (Nibble, PR-Nibble, HK-PR, rand-HK-PR) + evolving
sets + the Theorem-1 work-efficient parallel sweep cut, all expressed in the
paper's own primitive vocabulary (prefix sum / filter / sort) on fixed-
capacity frontiers — jit/vmap/shard_map-ready.  Sequential references in
:mod:`repro.core.seq`.
"""
from . import ops
from .frontier import (Frontier, EdgeBatch, singleton, expand, pack_unique,
                       compact_sorted, next_pow2, scatter_add_dense,
                       scatter_set_dense)
from .sweep import SweepResult, sweep_cut, sweep_cut_dense, sweep_cut_sparse
from .nibble import NibbleResult, nibble, nibble_fixedcap
from .pr_nibble import PRNibbleResult, pr_nibble, pr_nibble_fixedcap
from .pr_nibble_sparse import (PRNibbleSparseResult, PRNibbleSparseState,
                               pr_nibble_sparse, pr_nibble_sparse_fixedcap,
                               pr_nibble_sparse_init, pr_nibble_sparse_round,
                               pr_nibble_sparse_alive)
from .hk_pr import HKPRResult, hk_pr, hk_pr_fixedcap, psis
from .rand_hk_pr import RandHKPRResult, rand_hk_pr, poisson_cdf_table
from .evolving_sets import EvolvingSetsResult, evolving_sets
from .sparsevec import SparseVec, sv_empty, sv_lookup, sv_merge_add
from .batched import (BatchedDiffusionResult, BatchedClusterResult,
                      batched_pr_nibble, batched_hk_pr, batched_cluster,
                      batched_pr_nibble_fixedcap, batched_hk_pr_fixedcap,
                      batched_cluster_fixedcap, batched_sweep_cut)
from .batched_dist import (BatchedDistDiffusionResult, DistLaneState,
                           batched_dist_pr_nibble, batched_cluster_dist,
                           dist_lane_kernels)
from .batched_sparse import (BatchedSparseDiffusionResult,
                             BatchedSparseClusterResult,
                             batched_pr_nibble_sparse, batched_cluster_sparse,
                             batched_pr_nibble_sparse_fixedcap,
                             batched_cluster_sparse_fixedcap,
                             batched_sparse_sweep_cut, sparse_rows_to_dense,
                             sparse_lane_footprint, pick_backend)
from .ncp import NCPResult, ncp, ncp_batch
from . import seq

__all__ = [
    "ops",
    "Frontier", "EdgeBatch", "singleton", "expand", "pack_unique",
    "compact_sorted", "next_pow2",
    "scatter_add_dense", "scatter_set_dense",
    "SweepResult", "sweep_cut", "sweep_cut_dense", "sweep_cut_sparse",
    "NibbleResult", "nibble", "nibble_fixedcap",
    "PRNibbleResult", "pr_nibble", "pr_nibble_fixedcap",
    "PRNibbleSparseResult", "PRNibbleSparseState", "pr_nibble_sparse",
    "pr_nibble_sparse_fixedcap", "pr_nibble_sparse_init",
    "pr_nibble_sparse_round", "pr_nibble_sparse_alive",
    "HKPRResult", "hk_pr", "hk_pr_fixedcap", "psis",
    "RandHKPRResult", "rand_hk_pr", "poisson_cdf_table",
    "EvolvingSetsResult", "evolving_sets",
    "SparseVec", "sv_empty", "sv_lookup", "sv_merge_add",
    "BatchedDiffusionResult", "BatchedClusterResult",
    "batched_pr_nibble", "batched_hk_pr", "batched_cluster",
    "batched_pr_nibble_fixedcap", "batched_hk_pr_fixedcap",
    "batched_cluster_fixedcap", "batched_sweep_cut",
    "BatchedDistDiffusionResult", "DistLaneState",
    "batched_dist_pr_nibble", "batched_cluster_dist", "dist_lane_kernels",
    "BatchedSparseDiffusionResult", "BatchedSparseClusterResult",
    "batched_pr_nibble_sparse", "batched_cluster_sparse",
    "batched_pr_nibble_sparse_fixedcap", "batched_cluster_sparse_fixedcap",
    "batched_sparse_sweep_cut", "sparse_rows_to_dense",
    "sparse_lane_footprint", "pick_backend",
    "NCPResult", "ncp", "ncp_batch",
    "seq",
]

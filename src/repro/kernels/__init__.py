"""Pallas TPU kernels for the paper's compute hot-spots.

  scatter_accum — ordered scatter-add (fetchAdd), folded group by group
  prefix_scan   — blocked prefix sum (sweep-cut backbone)
  segment_merge — segmented left fold (sv_merge_add's run reduction)

``ops`` holds the layout wrappers and decides interpret mode, ``ref`` the
plain oracles.  Kernels compile for TPU; on other platforms they run under
``interpret=True``.  Drivers never import these directly — they dispatch
through :mod:`repro.core.ops`.
"""
from . import ops, ref
from .scatter_accum import scatter_fold_groups
from .prefix_scan import block_scan
from .segment_merge import fold_runs

__all__ = ["ops", "ref", "scatter_fold_groups", "block_scan", "fold_runs"]

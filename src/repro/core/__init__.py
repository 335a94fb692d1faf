# The paper's primary contribution — (k, eps)-coresets for decision trees
# of 2D signals (NeurIPS 2021) — implemented as a composable library:
# prefix statistics, the bi-criteria lower bound, the balanced partition,
# Caratheodory block compression, the Algorithm-5 query engine, plus
# streaming (merge-reduce) and mesh-distributed construction.
from .stats import PrefixStats, opt1_from_sums
from .slice_partition import slice_partition
from .balanced import BalancedPartition, balanced_partition
from .bicriteria import BicriteriaResult, bicriteria
from .caratheodory import block_representatives, caratheodory_reduce
from .coreset import SignalCoreset, signal_coreset, signal_coreset_to_size
from .fitting_loss import fitting_loss, true_loss, overlap_counts
from .segmentation import (Segmentation, greedy_tree, optimal_labels,
                           optimal_tree_dp, random_tree_segmentation,
                           segment_1d_dp)
from .streaming import StreamingBuilder, compose, recompress, weighted_signal_coreset
from .sharded import fitting_loss_batched, sharded_coreset

__all__ = [
    "PrefixStats", "opt1_from_sums", "slice_partition", "BalancedPartition",
    "balanced_partition", "BicriteriaResult", "bicriteria",
    "block_representatives", "caratheodory_reduce", "SignalCoreset",
    "signal_coreset", "signal_coreset_to_size", "fitting_loss", "true_loss",
    "overlap_counts",
    "Segmentation", "greedy_tree", "optimal_labels", "optimal_tree_dp",
    "random_tree_segmentation", "segment_1d_dp", "StreamingBuilder",
    "compose", "recompress", "weighted_signal_coreset",
    "fitting_loss_batched", "sharded_coreset",
]

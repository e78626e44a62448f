from .histogram import build_histogram, sub_hist
from .split import SplitParams, SplitResult, find_best_split
from .grower import GrowerConfig, TreeArrays, grow_tree
from .predict import predict_leaf_binned, add_score_from_leaves

__all__ = ["build_histogram", "sub_hist", "SplitParams", "SplitResult",
           "find_best_split", "GrowerConfig", "TreeArrays", "grow_tree",
           "predict_leaf_binned", "add_score_from_leaves"]

"""Share of the window in operations under ``lgbm/sum_repair``, wherever
nested: the siblings' subtraction in float32 pairs and each leaf's totals
from its own histogram, which is what the mend of the small-leaf sums runs on
the device outside the Mosaic calls and the split search."""
from benchmarks import leaf_reduce


def read(run):
    return leaf_reduce.scope_share(run, "lgbm/sum_repair")

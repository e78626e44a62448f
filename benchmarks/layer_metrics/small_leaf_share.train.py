"""Leaves of under 100 rows over all leaves of the window's trees (counters
``train.leaves_under_100_rows`` over ``train.leaves``, per tree on the
``lgbm/update/drain`` spans): whether the cell is in the regime where a leaf's
sums are a millionth of the root's."""
from benchmarks import leaf_reduce


def read(run):
    drains = leaf_reduce.window_drains(run)
    return None if drains is None else leaf_reduce.small_leaf_share(drains)

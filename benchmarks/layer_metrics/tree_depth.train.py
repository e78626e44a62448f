"""Mean over the window's trees of the deepest leaf's level (counter
``train.tree_depth``, per tree on the ``lgbm/update/drain`` spans): the rounds
a tree takes and the levels its validation rows walk follow it."""
from benchmarks import leaf_reduce


def read(run):
    drains = leaf_reduce.window_drains(run)
    return None if drains is None else leaf_reduce.mean_tree_depth(drains)

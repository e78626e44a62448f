"""Frontier rounds of the window's trees over their number (the counter
``train.frontier_rounds``, per tree on the ``lgbm/update/drain`` spans)."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "frontier_rounds_per_tree")

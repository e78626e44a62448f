"""Share of the window in operations under ``lgbm/split_search``, wherever nested
(the root's search and each round's 2k children)."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "split_search_share")

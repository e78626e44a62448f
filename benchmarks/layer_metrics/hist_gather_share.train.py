"""Share of the window in operations under ``lgbm/frontier_round/hist_gather``:
the smaller children's rows gathered into blocks for the histogram kernel."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "hist_gather_share")

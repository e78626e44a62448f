"""Share of the window in ``lgbm/frontier_round/select`` +
``lgbm/frontier_round/bookkeeping`` + ``lgbm/finalize``: the sorts and small
scatters around the row work."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "round_select_share")

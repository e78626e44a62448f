"""Share of the window in ``lgbm/gradients`` + ``lgbm/sample`` +
``lgbm/score_update`` + ``lgbm/valid_traverse``: the boosting step around the
grow program."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "boost_step_share")

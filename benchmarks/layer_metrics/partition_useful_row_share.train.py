"""``rows_selected / rows_passed`` over the window's trees: of the rows the
rounds passed, the share that belonged to a leaf being split."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "partition_useful_row_share")

"""Share of the window in operations under ``lgbm/frontier_round/partition`` (its
children ``decide``, ``rank`` and ``scatter`` included): the pass over every row
that each frontier round makes, whatever it splits."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "partition_share")

"""Host seconds of ``lgbm/booster/init/election``: the one-hot variant's
on-device micro-bench."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "election_s")

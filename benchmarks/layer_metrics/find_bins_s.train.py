"""Host seconds of the ``lgbm/dataset/construct/find_bins`` spans: bin mappers
from the row sample."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "find_bins_s")

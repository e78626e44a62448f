"""Host seconds of ``lgbm/dataset/construct/bin_values`` + ``.../reference_bin``
+ ``.../to_2d_float``: every row into bins, both data sets."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "bin_values_s")

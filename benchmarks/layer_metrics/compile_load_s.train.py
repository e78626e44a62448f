"""Seconds of the ``lgbm/compile`` spans that ended before the window:
compilation, or the load of a cached executable."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "compile_load_s")

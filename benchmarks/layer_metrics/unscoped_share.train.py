"""Share of the window in operations outside the Mosaic calls that have no
``lgbm/`` scope, an ambiguous key or a key the program's table lacks: the check
on the tracing itself."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "unscoped_share")

"""``lgbm/compile`` spans that start inside the window: 0, and the printed table
names the function and the span it happened under when it is not."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "compiles_in_window")

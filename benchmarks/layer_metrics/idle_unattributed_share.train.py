"""Of the device's idle time in the window, the share under no leaf span of the
program: idle time nothing explains."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "idle_unattributed_share")

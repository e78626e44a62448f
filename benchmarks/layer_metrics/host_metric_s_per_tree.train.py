"""Seconds of the ``lgbm/eval`` spans inside the window, less their
``lgbm/eval/wait`` children (the host waiting for the device to finish the
tree), over the window's trees: the scores' copy to the host and the metrics."""
from benchmarks import phase_reduce


def read(run):
    return phase_reduce.value(run, "host_metric_s_per_tree")

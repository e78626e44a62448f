"""Share of the window in operations under ``lgbm/valid_metric``: the
built-in binary metrics computed over the device-resident validation scores
(the AUC's sort and scans, the log-loss's softplus, their block sums).  None
against a program that names no such scope, whose metrics run on the host."""
from benchmarks import leaf_reduce


def read(run):
    return leaf_reduce.scope_share(run, "lgbm/valid_metric")

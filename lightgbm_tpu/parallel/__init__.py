"""Distributed training over JAX device meshes.

This package replaces the reference's entire ``src/network/`` layer
(hand-written Bruck allgather / recursive-halving reduce-scatter over TCP
sockets or MPI, ``network.cpp``, ``linkers_socket.cpp``, ``linkers_mpi.cpp``)
and its three parallel tree learners (``src/treelearner/
{data,feature,voting}_parallel_tree_learner.cpp``) with `shard_map` programs
over a `jax.sharding.Mesh`, where the communication patterns are single XLA
collectives riding ICI/DCN:

- histogram ReduceScatter        -> ``lax.psum`` / ``lax.psum_scatter``
- best-split Allreduce (max)     -> ``lax.pmax`` over a packed (gain, key)
- scalar GlobalSum / SyncUpBy*   -> ``lax.psum`` / ``lax.pmin`` / ``lax.pmax``

Multi-host bring-up (the reference's machine-list file + port handshake,
``linkers_socket.cpp``; Dask's cluster setup, ``python-package/lightgbm/
dask.py``) is ``jax.distributed.initialize`` + the standard TPU pod runtime.
"""
import importlib

from .mesh import default_mesh, free_network, init_distributed, set_network

# the rest on first use: ``GBDT`` imports ``.mesh`` for every learner, and
# the estimators would bring scikit-learn and SciPy (4 s) into every process
_LAZY = {
    "distributed_dataset": "..io.distributed",
    "train_distributed": ".trainer",
    "make_dp_train_step": ".data_parallel",
    "pad_rows_to_multiple": ".data_parallel",
    "shard_rows": ".data_parallel",
    "make_fp_train_step": ".feature_parallel",
    "pad_features_to_multiple": ".feature_parallel",
    "make_voting_train_step": ".voting_parallel",
    "DistLGBMClassifier": ".estimators",
    "DistLGBMRegressor": ".estimators",
}


def __getattr__(name):
    if name not in _LAZY:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(_LAZY[name], __name__), name)


__all__ = ["default_mesh", "init_distributed", "set_network",
           "free_network", "distributed_dataset", "train_distributed",
           "make_dp_train_step",
           "make_fp_train_step", "make_voting_train_step",
           "pad_rows_to_multiple", "pad_features_to_multiple", "shard_rows",
           "DistLGBMClassifier", "DistLGBMRegressor"]

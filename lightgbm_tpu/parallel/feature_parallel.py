"""Feature-parallel GBDT training step: features sharded over a mesh axis.

TPU-native re-design of ``FeatureParallelTreeLearner``
(``src/treelearner/feature_parallel_tree_learner.cpp``): the reference keeps
ALL rows on every rank and shards only the split *search* by feature
(bin-count-balanced assignment, ``:38-57``), then allreduce-maxes the
serialized ``SplitInfo`` (``parallel_tree_learner.h:191-214``) so every rank
applies the identical split locally.

Here the binned matrix itself is sharded ``[N, F/nf]`` (saving HBM as well
as work), per-shard bests are combined with a ``pmax`` + masked-``psum``
broadcast (see ``ops.grower._reduce_split_global``), and — because columns
are sharded, unlike the reference — the winning shard broadcasts its
partition decision with one ``[N]`` psum per split.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grower import GrowerConfig, grow_tree
from .mesh import FEATURE_AXIS


def make_fp_train_step(grower_cfg: GrowerConfig,
                       feature_meta: dict,
                       grad_fn: Callable,
                       learning_rate: float,
                       mesh: jax.sharding.Mesh,
                       axis_name: str = FEATURE_AXIS):
    """Build a jitted feature-parallel one-iteration training step.

    Inputs at call time:
      bins ``[N, F]`` (sharded over features), label/score/row_weight ``[N]``
      (replicated), fmask ``[F]`` full-width (replicated), key.
    feature_meta arrays stay FULL-width and replicated.
    Returns ``(new_score[N], TreeArrays)`` — both replicated.
    """
    n_shards = mesh.shape[axis_name]
    cfg = grower_cfg._replace(axis_name=axis_name, parallel_mode="feature",
                              num_shards=n_shards)
    fm = feature_meta

    def step(bins, label, score, row_weight, fmask, key):
        # shared grad_fn convention with make_dp_train_step:
        # (score, label, weight); sample weights are not
        # wired through this learner's step
        grad, hess = grad_fn(score, label, None)
        tree, node_assign = grow_tree(
            bins, grad, hess, row_weight, fmask,
            fm["num_bins"], fm["default_bins"], fm["nan_bins"],
            fm["is_categorical"], fm["monotone"], key, cfg)
        delta = tree.leaf_value * learning_rate
        has_split = tree.num_leaves > 1
        new_score = score + jnp.where(has_split, delta[node_assign], 0.0)
        return new_score, tree

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, axis_name), P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False)  # outputs replicated by construction (psum-reduced)
    jitted = jax.jit(sharded)

    @functools.wraps(jitted)
    def checked(bins, label, score, row_weight, fmask, key):
        if bins.shape[1] % n_shards:
            raise ValueError(
                f"feature count {bins.shape[1]} is not divisible by the "
                f"{n_shards}-way '{axis_name}' mesh axis; pad features (all-"
                f"constant columns bin to a single bin and are never chosen)")
        return jitted(bins, label, score, row_weight, fmask, key)
    return checked


def pad_features_to_multiple(f: int, k: int) -> int:
    """Features must divide the mesh axis; number of pad columns needed."""
    return (-f) % k

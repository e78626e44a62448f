"""Voting-parallel GBDT training step: data parallel with ~constant comm.

TPU-native re-design of ``VotingParallelTreeLearner``
(``src/treelearner/voting_parallel_tree_learner.cpp``): rows are sharded;
each shard proposes its local top-k split features (``top_k`` config), a
global vote elects 2k features per leaf (``GlobalVoting``, ``:151``), and
only the elected features' histograms are reduced (``CopyLocalHistogram``
+ ReduceScatter, ``:184,345``) — shrinking per-split communication from
``F×B`` to ``2k×B`` histogram rows.

Here the vote is a psum of one-hot ballots, the election is a replicated
``top_k`` over vote counts, and the elected histograms ride one gathered
psum (see ``ops.grower`` voting mode).  Local min-data/min-hessian gates are
scaled by ``1/num_shards`` like the reference (``:61-63``).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grower import GrowerConfig, grow_tree
from .mesh import DATA_AXIS


def make_voting_train_step(grower_cfg: GrowerConfig,
                           feature_meta: dict,
                           grad_fn: Callable,
                           learning_rate: float,
                           mesh: jax.sharding.Mesh,
                           top_k: int = 20,
                           axis_name: str = DATA_AXIS):
    """Build a jitted voting-parallel one-iteration training step.

    Same calling convention as ``make_dp_train_step`` (rows sharded over
    ``axis_name``); only elected histograms cross the interconnect.
    """
    n_shards = mesh.shape[axis_name]
    cfg = grower_cfg._replace(axis_name=axis_name, parallel_mode="voting",
                              top_k=top_k, num_shards=n_shards)
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
        in_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name),
                  P(), P()),
        out_specs=(P(axis_name), P()),
        check_vma=False)
    jitted = jax.jit(sharded)

    @functools.wraps(jitted)
    def checked(bins, label, score, row_weight, fmask, key):
        if bins.shape[0] % n_shards:
            raise ValueError(
                f"row count {bins.shape[0]} is not divisible by the "
                f"{n_shards}-way '{axis_name}' mesh axis")
        return jitted(bins, label, score, row_weight, fmask, key)
    return checked

"""Data-parallel GBDT training step: rows sharded over a mesh axis.

TPU-native re-design of ``DataParallelTreeLearner``
(``src/treelearner/data_parallel_tree_learner.cpp``): the reference shards
rows across machines, builds local histograms, ReduceScatters the packed
histogram buffer so each rank owns full histograms for a feature block
(``:155-173``), searches splits on its block, then Allreduce-maxes the
serialized ``SplitInfo`` (``parallel_tree_learner.h:191-214``).

Here the same dataflow is one `shard_map` program: the grower runs on each
shard with ``GrowerConfig.axis_name`` set, and the reference's dataflow maps
onto collectives exactly (ops/grower.py ``reduce_hist`` /
``_reduce_split_global``):

- per split, local histograms join via ``lax.psum_scatter`` over the feature
  axis, so each shard RECEIVES, STORES and SEARCHES only its owned feature
  block — comm volume F*B/ndev per device per split (a full ``psum`` moves
  F*B and was the round-2 shape), and the histogram-subtraction store
  shrinks by 1/ndev too;
- each shard's local best split then rides a tiny ``pmax``-based SplitInfo
  allreduce (``_reduce_split_global`` = SyncUpGlobalBestSplit), after which
  every shard applies the identical split to its local rows — the
  reference's local ``DataPartition::Split``.

Paths that need a full-width histogram on every shard (EFB bundle
expansion, forced splits, CEGB-lazy) fall back to the full ``psum``.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.grower import GrowerConfig, grow_tree
from .mesh import DATA_AXIS


def make_dp_train_step(grower_cfg: GrowerConfig,
                       feature_meta: dict,
                       grad_fn: Optional[Callable],
                       learning_rate: float,
                       mesh: jax.sharding.Mesh,
                       axis_name: str = DATA_AXIS,
                       num_class: int = 1,
                       external_grads: bool = False,
                       efb=None):
    """Build a jitted data-parallel one-iteration training step.

    Args:
      grower_cfg: static grower config; its ``axis_name`` is overridden.
      feature_meta: dict with replicated per-feature arrays
        (num_bins, default_bins, nan_bins, is_categorical, monotone).
      grad_fn: elementwise shard-local objective gradient —
        ``(score[n], label[n], weight[n]|None) -> (grad[n], hess[n])`` for
        one class, or ``(score[K,n], label, weight) -> ([K,n], [K,n])``
        when ``num_class > 1`` (softmax couples the classes, so gradients
        come from the full score matrix).
      learning_rate: shrinkage applied to leaf values in the score update.
      num_class: trees grown per iteration (one per class, in one
        ``lax.scan`` so the program compiles once).

    Returns a function
      ``(bins[N,F], label[N], score[N] or [K,N], row_weight[N], fmask[F],
         key, weight=None) -> (new_score, TreeArrays)``
    with rows sharded over ``axis_name`` and the tree(s) replicated
    (leaf arrays gain a leading class axis when ``num_class > 1``).
    ``row_weight`` carries the pad/bag mask; ``weight`` (or None) the user
    sample weights, applied inside the objective like the single-process
    engine (counts stay mask-based).
    """
    cfg = grower_cfg._replace(axis_name=axis_name)
    fm = feature_meta
    K = num_class

    def one_tree(grad, hess, bins, row_weight, fmask, key):
        tree, node_assign = grow_tree(
            bins, grad, hess, row_weight, fmask,
            fm["num_bins"], fm["default_bins"], fm["nan_bins"],
            fm["is_categorical"], fm["monotone"], key, cfg, efb=efb)
        delta = tree.leaf_value * learning_rate
        has_split = tree.num_leaves > 1
        return jnp.where(has_split, delta[node_assign], 0.0), tree

    def grow_all(grads, hesses, bins, score, row_weight, fmask, key):
        if K == 1:
            d, tree = one_tree(grads, hesses, bins, row_weight, fmask, key)
            return score + d, tree

        def body(carry, xs):
            g, h, k = xs
            d, tree = one_tree(g, h, bins, row_weight, fmask, k)
            return carry, (d, tree)

        keys = jax.random.split(key, K)
        _, (deltas, trees) = jax.lax.scan(
            body, 0, (grads, hesses, keys))
        return score + deltas, trees

    score_spec = P(axis_name) if K == 1 else P(None, axis_name)
    n_shards = mesh.shape[axis_name]

    def check_rows(n):
        if n % n_shards:
            raise ValueError(
                f"row count {n} is not divisible by the "
                f"{n_shards}-way '{axis_name}' mesh axis; pad rows with "
                f"pad_rows_to_multiple() and zero row_weight for pad rows")

    if external_grads:
        # gradients arrive precomputed (host-side rank objectives, GOSS /
        # bagging amplification applied by the caller)
        def step_ex(bins, grads, hesses, score, row_weight, fmask, key):
            return grow_all(grads, hesses, bins, score, row_weight, fmask,
                            key)

        sharded = jax.shard_map(
            step_ex, mesh=mesh,
            in_specs=(P(axis_name), score_spec, score_spec, score_spec,
                      P(axis_name), P(), P()),
            out_specs=(score_spec, P()),
            check_vma=False)
        jitted = jax.jit(sharded)

        def checked_ex(bins, grads, hesses, score, row_weight, fmask, key):
            check_rows(bins.shape[0])
            return jitted(bins, grads, hesses, score, row_weight, fmask, key)
        return checked_ex

    def step(bins, label, score, row_weight, weight, fmask, key):
        grads, hesses = grad_fn(score, label, weight)
        return grow_all(grads, hesses, bins, score, row_weight, fmask, key)

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), score_spec, P(axis_name),
                  P(axis_name), P(), P()),
        out_specs=(score_spec, P()),
        check_vma=False)  # tree outputs are replicated by construction (psum)
    jitted = jax.jit(sharded)

    def checked(bins, label, score, row_weight, fmask, key, weight=None):
        check_rows(bins.shape[0])
        if weight is None:
            weight = jnp.ones_like(label)
        return jitted(bins, label, score, row_weight, weight, fmask, key)
    return checked


def shard_rows(mesh: jax.sharding.Mesh, axis_name: str = DATA_AXIS):
    """NamedSharding placing the leading (row) axis on the mesh."""
    return jax.sharding.NamedSharding(mesh, P(axis_name))


def pad_rows_to_multiple(n: int, k: int) -> int:
    """Rows must divide the mesh axis; pad count (weights 0 for pad rows)."""
    return (-n) % k

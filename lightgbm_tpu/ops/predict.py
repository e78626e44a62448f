"""Device-side tree traversal over binned data.

Used for training/validation score updates: validation sets are binned with
the training set's mappers, so bin-threshold comparison is exactly equivalent
to the reference's raw-value traversal (``tree.h:133``).  The tree is walked
node by node, not row by row: every grower numbers node ``j`` as the ``j``-th
split applied, so a node's children carry a higher index than the node, and
one pass over the nodes in index order, each applied to all rows at once,
takes every row to its leaf.  A node's fields are scalars and its bins one
contiguous column; nothing is gathered per row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..io.efb import decode_bundle_column
from .grower import TreeArrays


def predict_leaf_binned(tree: TreeArrays, bins: jax.Array, nan_bins: jax.Array,
                        efb=None) -> jax.Array:
    """Leaf index per row for binned features ``[N, F]``.

    ``efb``: optional static ``(feat_bundle, feat_off, num_bins)`` arrays
    when ``bins`` is an EFB bundle matrix (io/efb.py) — the per-feature bin
    decodes through the uniform ``col - off + 1`` range mapping."""
    n = bins.shape[0]
    cols = bins.T                   # feature-major: a column is a contiguous row
    nan_bins = jnp.asarray(nan_bins, jnp.int32)
    if efb is not None:
        fb, fo, fnb = (jnp.asarray(a.astype("int32")) for a in efb)
    cw = tree.cat_bits.shape[1]

    def step(j, cur):
        feat = tree.split_feature[j]
        col = jax.lax.dynamic_index_in_dim(
            cols, fb[feat] if efb is not None else feat, keepdims=False
        ).astype(jnp.int32)                                  # [N]
        if efb is not None:
            col = decode_bundle_column(col, fo[feat], fnb[feat])
        nb = nan_bins[feat]
        is_miss = (col == nb) & (nb >= 0)
        # categorical: bin-bitset membership (one-hot and sorted subsets);
        # word min(col >> 5, cw - 1) of the node's bit set, by selects
        bits = tree.cat_bits[j]                              # [CW]
        word = bits[0]
        for w in range(1, cw):
            word = jnp.where(col >> 5 >= w, bits[w], word)
        cat_left = ((word >> (col & 31)) & 1) == 1
        goes_left = jnp.where(tree.is_cat_split[j], cat_left,
                              jnp.where(is_miss, tree.default_left[j],
                                        col <= tree.threshold[j]))
        nxt = jnp.where(goes_left, tree.left_child[j], tree.right_child[j])
        return jnp.where(cur == j, nxt, cur)

    # rows stand at node 0, or at leaf 0 (~0) where the tree did not split
    init = jnp.full(n, jnp.where(tree.num_leaves > 1, 0, -1), jnp.int32)
    final = jax.lax.fori_loop(0, tree.num_leaves - 1, step, init)
    return ~final


def add_score_from_leaves(score: jax.Array, leaf_idx: jax.Array,
                          leaf_value: jax.Array) -> jax.Array:
    """Score update by leaf gather (the reference's by-partition
    ``ScoreUpdater::AddScore``, ``score_updater.hpp:88``)."""
    return score + leaf_value[leaf_idx]

"""DART: Dropouts meet Multiple Additive Regression Trees
(reference ``src/boosting/dart.hpp``).

Per iteration: a random subset of existing trees is "dropped" (score
contributions subtracted), the new tree is fit against the reduced scores, and
both the new tree and the dropped trees are re-weighted
(``DroppingTrees`` ``dart.hpp:97``, ``Normalize`` ``:158``).  Dropped-tree
score deltas are recomputed by device-side binned traversal (tree arrays are
tiny and kept on device) instead of cached per-tree prediction buffers.
"""
from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from ..ops.predict import predict_leaf_binned
from ..utils.random_gen import Random
from .gbdt import GBDT


class DART(GBDT):
    def init_train(self, train_data):
        super().init_train(train_data)
        self._device_trees: List = []            # per-model TreeArrays
        self._tree_weights: List[float] = []     # current scale of each model
        self._rng = Random(self.config.drop_seed)
        self.shrinkage_rate = 1.0                # DART applies lr via normalization

    # -- helpers ------------------------------------------------------------
    def _tree_score_delta(self, model_idx: int, bins, scale: float):
        ta = self._device_trees[model_idx]
        leaf = predict_leaf_binned(ta, bins, self._dd.nan_bins, efb=self._dd.efb)
        vals = ta.leaf_value * scale
        # the training score has no rows that pad the mesh; the bins may
        return vals[leaf][:self.train_data.num_data]

    def train_one_iter(self, grad=None, hess=None):
        cfg = self.config
        K = self.num_tree_per_iteration
        n_models = len(self.models)
        n_iters_done = n_models // max(1, K)

        # --- choose drop set (dart.hpp:97) ---
        drop_iters: List[int] = []
        if n_iters_done > 0 and self._rng.next_float() >= cfg.skip_drop:
            if cfg.uniform_drop:
                drop_prob = 1.0 / max(1, n_iters_done)
                for i in range(n_iters_done):
                    if self._rng.next_float() < max(drop_prob, cfg.drop_rate):
                        drop_iters.append(i)
            else:
                for i in range(n_iters_done):
                    if self._rng.next_float() < cfg.drop_rate:
                        drop_iters.append(i)
            if cfg.max_drop > 0 and len(drop_iters) > cfg.max_drop:
                sel = np.random.default_rng(self._rng.next_int(0, 1 << 30)).choice(
                    len(drop_iters), cfg.max_drop, replace=False)
                drop_iters = [drop_iters[i] for i in sorted(sel)]

        # --- subtract dropped trees from scores ---
        for it in drop_iters:
            for k in range(K):
                mi = it * K + k
                w = self._tree_weights[mi]
                self._train_score = self._train_score.at[k].add(
                    -self._tree_score_delta(mi, self._dd.bins, w))
                for vi in range(len(self.valid_sets)):
                    ta = self._device_trees[mi]
                    leaf = predict_leaf_binned(ta, self._valid_bins(vi),
                                               self._dd.nan_bins, efb=self._dd.efb)
                    self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                        -(ta.leaf_value * w)[leaf])

        n_before = len(self.models)
        stop = super().train_one_iter(grad, hess)

        # --- normalize (dart.hpp:158) ---
        k_drop = len(drop_iters)
        lr = self.config.learning_rate
        if self.config.xgboost_dart_mode:
            new_scale = lr / (1.0 + lr)                 # xgboost mode
            old_factor = 1.0 / (1.0 + lr)
        else:
            new_scale = lr / (k_drop + 1.0) if k_drop > 0 else lr
            old_factor = k_drop / (k_drop + 1.0) if k_drop > 0 else 1.0

        # scale the newly-added trees by new_scale (they were added with
        # weight 1.0 by the base class since shrinkage_rate == 1)
        for mi in range(n_before, len(self.models)):
            self.models[mi].shrink(new_scale)
            self._tree_weights[mi] = new_scale
            k = mi - n_before
            adj = new_scale - 1.0
            ta = self._device_trees[mi]
            self._train_score = self._train_score.at[k].add(
                self._tree_score_delta(mi, self._dd.bins, adj))
            for vi in range(len(self.valid_sets)):
                leaf = predict_leaf_binned(ta, self._valid_bins(vi),
                                               self._dd.nan_bins, efb=self._dd.efb)
                self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                    (ta.leaf_value * adj)[leaf])

        # re-add dropped trees with reduced weight
        for it in drop_iters:
            for k in range(K):
                mi = it * K + k
                old_w = self._tree_weights[mi]
                new_w = old_w * old_factor
                self.models[mi].shrink(old_factor)
                self._tree_weights[mi] = new_w
                self._train_score = self._train_score.at[k].add(
                    self._tree_score_delta(mi, self._dd.bins, new_w))
                for vi in range(len(self.valid_sets)):
                    ta = self._device_trees[mi]
                    leaf = predict_leaf_binned(ta, self._valid_bins(vi),
                                               self._dd.nan_bins, efb=self._dd.efb)
                    self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                        (ta.leaf_value * new_w)[leaf])
        return stop

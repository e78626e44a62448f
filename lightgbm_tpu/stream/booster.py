"""Streaming boosting engines: GBDT/GOSS over a host-resident bin matrix.

``StreamGBDT`` keeps the training loop's per-row state on the HOST — raw
scores ``[K, N]`` float32, gradients/hessians, bagging masks, leaf
assignments — and drives ``StreamTreeGrower`` for tree growth, so the only
device residents are the streamed row blocks (bounded by the
``max_bin_matrix_bytes`` budget), the ``[L, F, B, 3]`` histogram store and
the per-feature metadata.  Gradients are computed per row block from the
host scores (one compiled objective program per block shape), matching the
in-HBM engine's elementwise objective math row-for-row.

Scope (v1, checked loudly in ``init_train``): serial single-process
training (multi-process streaming goes through
``parallel.trainer.train_distributed``), built-in elementwise or
renew-style objectives plus custom fobj, bagging (incl. pos/neg) and GOSS,
categorical features, basic monotone constraints, feature_fraction
(bytree + bynode), extra_trees, max_depth.  Not served: linear trees,
CEGB, interaction constraints, forced splits, monotone
intermediate/advanced, ranking objectives (query-coupled gradients), DART
and RF boosting.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..config import Config
from ..io.dataset import Dataset
from ..metric import create_metrics
from ..models.gbdt import GBDT, bag_mask_from_uniform
from ..obs import get_tracer
from ..obs import health as obs_health
from ..models.goss import goss_mask_from_importance
from ..models.tree import Tree
from ..objective import create_objective
from ..utils.log import Log, LightGBMError, check
from ..utils.random_gen import key_for_iteration
from .grower import StreamTreeGrower, make_shards
from .pipeline import PipelineStats


def stream_gradients(objective, score: np.ndarray, label_np, weight_np,
                     block_rows: int):
    """Per-block objective gradients from host-resident scores.

    THE streaming gradient loop (single-process booster AND distributed
    trainer — one copy, so the chunking/objective math cannot drift
    between the paths whose parity the subsystem guarantees).  ``score``
    is host ``[K, n]`` float32; returns host ``(g, h)`` of the same shape.
    """
    import jax.numpy as jnp
    if objective is None:
        raise LightGBMError("objective is None; provide custom grad/hess")
    K, n = score.shape
    g = np.empty((K, n), np.float32)
    h = np.empty((K, n), np.float32)
    for s in range(0, n, block_rows):
        e = min(s + block_rows, n)
        sc = jnp.asarray(score[:, s:e])
        lab = jnp.asarray(label_np[s:e]) if label_np is not None else None
        w = jnp.asarray(weight_np[s:e]) if weight_np is not None else None
        if K > 1:
            gg, hh = objective.get_gradients_multi(sc, lab, w)
        else:
            gg, hh = objective.get_gradients(sc[0], lab, w)
            gg, hh = gg[None, :], hh[None, :]
        g[:, s:e] = np.asarray(gg, np.float32)
        h[:, s:e] = np.asarray(hh, np.float32)
    return g, h


def stream_goss_sample(cfg: Config, iteration: int, imp: np.ndarray,
                       lo: int = 0, hi: "int | None" = None):
    """(mask, amplify) host arrays for rows ``[lo:hi)`` of the global
    order, from the GLOBAL per-row importance ``imp`` — the one streaming
    implementation of the in-HBM GOSS keying (exact global top-k +
    seeded tail draw, ``goss_mask_from_importance``)."""
    import jax
    import jax.numpy as jnp
    n_total = imp.shape[0]
    key = key_for_iteration(cfg.bagging_seed, iteration)
    mask, amplify = goss_mask_from_importance(
        cfg, jnp.asarray(imp), jax.random.uniform(key, (n_total,)),
        max(1, int(cfg.top_rate * n_total)))
    mask = np.asarray(mask, np.float32)
    amplify = np.asarray(amplify, np.float32)
    if lo or hi is not None:
        mask, amplify = mask[lo:hi], amplify[lo:hi]
    return mask, amplify


def predict_leaf_blocks(predict_fn, matrix) -> np.ndarray:
    """Leaf index per row of a host-resident matrix, one block at a time
    (over-budget validation sets — shared by the booster and the
    distributed trainer)."""
    out = np.empty(matrix.num_data, np.int32)
    for b in range(matrix.num_blocks):
        sl = matrix.block_slice(b)
        out[sl] = np.asarray(predict_fn(matrix.block(b)))
    return out


def stream_bag_mask(cfg: Config, iteration: int, n_global: int, label_np,
                    lo: int = 0, hi: "int | None" = None) -> np.ndarray:
    """Host bagging mask over rows ``[lo:hi)`` of the GLOBAL row order.

    THE one streaming implementation of the in-HBM keying
    (``key_for_iteration(bagging_seed, it // bagging_freq)`` ->
    ``bag_mask_from_uniform``): the single-process booster draws over its
    whole dataset (lo=0, hi=None) and the distributed trainer slices its
    rank's window of the same global draw — both must stay byte-identical
    to the device path for multi-process parity, so the formula lives
    once here."""
    import jax
    import jax.numpy as jnp
    key = key_for_iteration(cfg.bagging_seed, iteration // cfg.bagging_freq)
    u = jax.random.uniform(key, (n_global,))
    if lo or hi is not None:
        u = u[lo:hi]
    lab = jnp.asarray(label_np) if label_np is not None else None
    return np.asarray(bag_mask_from_uniform(cfg, u, lab), np.float32)


def _finite_stats(a) -> dict:
    """Host-side sentinel stats (the streaming twin of the device
    reductions in ``GBDT._health_stats_fn``)."""
    a = np.asarray(a, np.float32).ravel()
    finite = np.isfinite(a)
    mx = float(np.abs(a[finite]).max()) if finite.any() else 0.0
    return {"finite_frac": float(finite.mean()), "max_abs": mx}


class StreamGBDT(GBDT):
    """Out-of-core GBDT engine (see module docstring)."""

    # ------------------------------------------------------------------
    def init_train(self, train_data: Dataset) -> None:
        cfg = self.config
        self.train_data = train_data
        plan = train_data.stream_plan()
        check(plan is not None,
              "StreamGBDT needs a Dataset whose stream_plan() streams "
              "(set max_bin_matrix_bytes/stream_rows)")
        self._plan = plan
        self._check_supported(cfg)

        if self.objective is None:
            self.objective = create_objective(cfg)
        if self.objective is not None:
            if getattr(self.objective, "is_ranking", False):
                raise LightGBMError(
                    "out-of-core streaming does not support ranking "
                    "objectives (query-coupled gradients cannot be computed "
                    "per row block)")
            self.objective.init(train_data.metadata, train_data.num_data)
            self.num_tree_per_iteration = \
                self.objective.num_model_per_iteration
        else:
            self.num_tree_per_iteration = max(1, cfg.num_class)
        self.max_feature_idx = train_data.num_total_features - 1
        self.train_metrics = create_metrics(cfg)
        for m in self.train_metrics:
            m.init(train_data.metadata, train_data.num_data)

        # feature metadata WITHOUT bins: the matrix stays in host RAM
        self._dd = train_data.device_meta()
        md = train_data.metadata
        self._label_np = (np.asarray(md.label, np.float32)
                          if md.label is not None else None)
        self._weight_np = (np.asarray(md.weight, np.float32)
                           if md.weight is not None else None)
        K = self.num_tree_per_iteration
        n = train_data.num_data

        # boost from average / init_score (host scores)
        init = np.zeros((K, n), dtype=np.float32)
        md_init = md.init_score
        self.init_scores = [0.0] * K
        if md_init is not None:
            init += md_init.reshape(-1, n).astype(np.float32)
        elif cfg.boost_from_average and self.objective is not None:
            for k in range(K):
                s = self.objective.boost_from_score(k)
                self.init_scores[k] = s
                init[k] += s
        self._train_score = init
        self._grower_cfg = self._make_grower_cfg()

        self.stream_stats = PipelineStats()
        self._matrix = train_data.host_bin_matrix(plan)
        meta = {k: np.asarray(getattr(self._dd, k)) for k in
                ("num_bins", "default_bins", "nan_bins", "is_categorical",
                 "monotone")}
        self._stream_grower = StreamTreeGrower(
            make_shards([self._matrix], plan.prefetch, self.stream_stats),
            meta, self._grower_cfg)
        Log.info(
            "out-of-core streaming: %.1f MB bin matrix vs %s budget -> "
            "%d blocks of %d rows (prefetch %d, ~%.1f MB device-resident)",
            plan.total_bytes / 1e6,
            ("%.1f MB" % (plan.budget_bytes / 1e6) if plan.budget_bytes
             else "stream_rows"),
            plan.num_blocks, plan.block_rows, plan.prefetch,
            (plan.prefetch + 1) * self._matrix.block_nbytes / 1e6)

    @staticmethod
    def _check_supported(cfg: Config) -> None:
        bad = []
        if cfg.linear_tree:
            bad.append("linear_tree")
        if cfg.tree_learner != "serial":
            bad.append("tree_learner=%s (single-process streaming is "
                       "serial; multi-process goes through "
                       "parallel.train_distributed)" % cfg.tree_learner)
        if cfg.interaction_constraints:
            bad.append("interaction_constraints")
        if cfg.forcedsplits_filename:
            bad.append("forcedsplits_filename")
        if (cfg.cegb_tradeoff * cfg.cegb_penalty_split > 0
                or cfg.cegb_penalty_feature_lazy
                or cfg.cegb_penalty_feature_coupled):
            bad.append("cegb penalties")
        if (any(v != 0 for v in cfg.monotone_constraints)
                and cfg.monotone_constraints_method != "basic"):
            bad.append("monotone_constraints_method="
                       + cfg.monotone_constraints_method)
        if bad:
            raise LightGBMError(
                "out-of-core streaming does not support: " + ", ".join(bad))

    # ------------------------------------------------------------------
    def add_valid_data(self, valid_data: Dataset, name: str) -> None:
        super().add_valid_data(valid_data, name)
        # host scores (the base stored a device array; np.asarray of a jax
        # array is a read-only view — copy for in-place updates)
        self._valid_scores[-1] = np.array(self._valid_scores[-1],
                                          np.float32)

    # ------------------------------------------------------------------
    def _compute_gradients_stream(self):
        """Per-block objective gradients from the host-resident scores
        (``stream_gradients``, shared with the distributed trainer)."""
        return stream_gradients(self.objective, self._train_score,
                                self._label_np, self._weight_np,
                                self._plan.block_rows)

    def _stream_row_sample(self, iteration: int, g, h):
        """Bagging mask + amplified gradients, host-side; the uniform draw
        and mask formula are byte-identical to the in-HBM path
        (``stream_bag_mask``, shared with the distributed trainer)."""
        cfg = self.config
        n = self.train_data.num_data
        need = cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0 or
                                         cfg.pos_bagging_fraction < 1.0 or
                                         cfg.neg_bagging_fraction < 1.0)
        if not need:
            return None, g, h
        if iteration % cfg.bagging_freq == 0 or \
                getattr(self, "_bag_mask_np", None) is None:
            self._bag_mask_np = stream_bag_mask(cfg, iteration, n,
                                                self._label_np)
        mask = self._bag_mask_np
        return mask, g * mask[None, :], h * mask[None, :]

    # ------------------------------------------------------------------
    def _valid_leaf_stream(self, vi: int, tree_arrays):
        """Leaf index of every validation row — streamed block-wise when the
        valid set itself is over budget, device-resident otherwise."""
        import jax
        import jax.numpy as jnp
        from ..ops.predict import predict_leaf_binned

        if not hasattr(self, "_valid_stream"):
            self._valid_stream = {}
            self._vpredict = jax.jit(
                lambda ta, b: predict_leaf_binned(ta, b, self._dd.nan_bins))
        if vi not in self._valid_stream:
            vset = self.valid_sets[vi]
            vplan = vset.stream_plan()
            if vplan is None:
                self._valid_stream[vi] = ("device",
                                          jnp.asarray(vset.bins))
            else:
                self._valid_stream[vi] = ("host",
                                          vset.host_bin_matrix(vplan))
        kind, store = self._valid_stream[vi]
        ta_dev = jax.tree.map(jnp.asarray, tree_arrays)
        if kind == "device":
            return np.asarray(self._vpredict(ta_dev, store))
        return predict_leaf_blocks(
            lambda blk: self._vpredict(ta_dev, jnp.asarray(blk)), store)

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        cfg = self.config
        K = self.num_tree_per_iteration
        n = self.train_data.num_data
        it = self.iter_

        if self._stop_flag:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True

        obs = self._obs
        tracer = get_tracer()
        tracer.begin("lgbm/update", iteration=it)
        try:
            should_stop = self._stream_one_iter(grad, hess, it, tracer)
        finally:
            tracer.end("lgbm/update")
        if obs is not None:
            obs.iteration_event(it, trees=K)
        elif self._health_enabled:
            obs_health.set_status(stage="stream", iteration=it)
        return should_stop

    def _stream_one_iter(self, grad, hess, it: int, tracer) -> bool:
        cfg = self.config
        K = self.num_tree_per_iteration
        n = self.train_data.num_data
        obs = self._obs
        with tracer.span("lgbm/update/gradients"):
            if grad is None or hess is None:
                g, h = self._compute_gradients_stream()
            else:
                g = np.asarray(grad, np.float32).reshape(K, n)
                h = np.asarray(hess, np.float32).reshape(K, n)

        mask, g, h = self._stream_row_sample(it, g, h)
        rw = mask if mask is not None else np.ones(n, np.float32)
        fmask = np.asarray(self._feature_mask(it), np.float32)
        self._prev_scores = (self._train_score.copy(),
                             [v.copy() for v in self._valid_scores])

        should_stop = True
        for k in range(K):
            with tracer.span("lgbm/update/grow_dispatch"):
                tree_arrays, node_assign = self._stream_grower.grow(
                    g[k], h[k], rw, fmask,
                    key_for_iteration(cfg.seed, it, salt=k + 1))
            nl = int(tree_arrays.num_leaves)
            if self._health_due(it, k):
                # streaming gradients/leaves are already host numpy —
                # check in line (no device round-trip to ride)
                obs_health.check_numeric(
                    {"grad": _finite_stats(g[k]),
                     "hess": _finite_stats(h[k]),
                     "leaf_value": _finite_stats(tree_arrays.leaf_value)},
                    iteration=it, kind="stream",
                    log=obs.log if obs is not None else None)
            if nl > 1:
                should_stop = False
            if obs is not None:
                obs.tree_event(
                    it, num_leaves=nl,
                    split_gains=[float(v) for v in np.asarray(
                        tree_arrays.split_gain)[:max(0, nl - 1)]])
            tree = Tree.from_arrays(tree_arrays, self.train_data,
                                    learning_rate=1.0)

            # leaf renewal for L1-style objectives (host state is already
            # exactly what renew wants: per-row leaf ids + scores)
            if (self.objective is not None
                    and self.objective.need_renew_tree_output() and nl > 1):
                new_vals = self.objective.renew_leaf_values(
                    node_assign, self._train_score[k].astype(np.float64),
                    tree.leaf_value.copy(), nl)
                tree.leaf_value = np.asarray(new_vals, np.float64)
                tree_arrays = tree_arrays._replace(
                    leaf_value=np.asarray(tree.leaf_value, np.float32))

            tree.shrink(self.shrinkage_rate)
            if it == 0 and self.init_scores[k] != 0.0:
                if nl > 1:
                    tree.add_bias(self.init_scores[k])
                else:
                    tree.leaf_value = np.full_like(tree.leaf_value,
                                                   self.init_scores[k])

            with tracer.span("lgbm/update/score_dispatch"):
                if nl > 1:
                    delta = (np.asarray(tree_arrays.leaf_value, np.float32)
                             * np.float32(self.shrinkage_rate))
                    self._train_score[k] += delta[node_assign]
                    for vi in range(len(self.valid_sets)):
                        vleaf = self._valid_leaf_stream(vi, tree_arrays)
                        self._valid_scores[vi][k] += delta[vleaf]
            self.models.append(tree)
            self._tree_weights.append(self.shrinkage_rate)

        self.iter_ += 1
        if should_stop:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            self._stop_flag = True
        return should_stop

    # ------------------------------------------------------------------
    def continue_from(self, prev: "GBDT") -> None:
        super().continue_from(prev)
        # the base warms scores into device arrays; streaming keeps host f32
        # (np.array, not asarray: jax arrays view as read-only)
        self._train_score = np.array(self._train_score, np.float32)
        self._valid_scores = [np.array(v, np.float32)
                              for v in self._valid_scores]

    def rollback_one_iter(self) -> None:
        # base pops _device_trees too; streaming never fills it, so guard
        if self.iter_ <= 0:
            return
        if self._prev_scores is None:
            raise LightGBMError(
                "rollback history exhausted (only one step kept)")
        K = self.num_tree_per_iteration
        self.models = self.models[:-K]
        self._tree_weights = self._tree_weights[:-K]
        self._ens_cache = None
        self.iter_ -= 1
        self._empty_by_iter.pop(self.iter_, None)
        self._stop_flag = False
        self._train_score, self._valid_scores = self._prev_scores
        self._prev_scores = None


class StreamGOSS(StreamGBDT):
    """GOSS sampling over the streaming engine: the top-rate cut and
    random-tail draw reuse ``goss_mask_from_importance`` with the same
    iteration keying as the in-HBM GOSS, so sampled row sets match."""

    def _stream_row_sample(self, iteration: int, g, h):
        cfg = self.config
        if cfg.top_rate + cfg.other_rate >= 1.0:
            return None, g, h
        imp = np.sum(np.abs(g * h), axis=0)
        mask, amplify = stream_goss_sample(cfg, iteration, imp)
        amplify = amplify[None, :]
        return mask, g * amplify, h * amplify

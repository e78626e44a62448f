"""GBDT: the boosting engine.

TPU-native re-design of the reference ``GBDT`` (``src/boosting/gbdt.cpp``):
same training-loop semantics — boost-from-average (``gbdt.cpp:344``),
per-iteration gradients (``:170``), bagging (``:228``), one tree per class per
iteration, shrinkage, score-cache updates (``:491``), early stopping
(``:517-575``), model text IO (``gbdt_model_text.cpp``) — but each boosting
iteration's compute (gradients → bagging mask → tree growth → score update)
runs as compiled JAX programs with device-resident scores, and the tree
learner is the single-program grower in ``ops/grower.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..io.dataset import Dataset, DeviceData
from ..obs import TrainTelemetry, get_tracer
from ..obs import costs as obs_costs
from ..obs import health as obs_health
from ..obs import metrics as obs_metrics
from ..metric import create_metrics
from ..objective import ObjectiveFunction, create_objective
from ..ops.grower import GrowerConfig, TreeArrays, grow_tree
from ..ops.predict import predict_leaf_binned
from ..ops.split import SplitParams
from ..utils.log import Log, check, LightGBMError
from ..utils.random_gen import key_for_iteration
from .tree import Tree

# rows per densified block when predicting on scipy.sparse input: bounds
# peak host memory at block_rows * F floats (reference predicts CSR rows
# one at a time; here a block feeds the device ensemble predictor)
_SPARSE_PREDICT_BLOCK = 65536


from ..io.dataset import _is_sparse as _is_sparse_mat


def _blockwise_sparse(X, fn):
    """Apply ``fn`` (a dense-matrix predict) over densified row blocks of a
    scipy.sparse matrix and concatenate the results."""
    X = X.tocsr()
    if X.shape[0] == 0:
        return fn(np.zeros((0, X.shape[1]), np.float64))
    outs = [fn(np.asarray(X[s:s + _SPARSE_PREDICT_BLOCK].toarray(), np.float64))
            for s in range(0, X.shape[0], _SPARSE_PREDICT_BLOCK)]
    return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)


class GBDT:
    """Gradient Boosting Decision Tree engine (reference ``gbdt.h:35``)."""

    def __init__(self, config: Config, train_data: Optional[Dataset] = None,
                 objective: Optional[ObjectiveFunction] = None):
        self.config = config
        self.train_data: Optional[Dataset] = None
        self.objective = objective
        # telemetry hook (obs_telemetry): None keeps the off path at one
        # attribute check per iteration (<2% overhead budget)
        self._obs = TrainTelemetry(config) if config.obs_telemetry else None
        # live health plane: numeric sentinels every N rounds + the
        # /metrics //healthz exposition server (obs_health_port or the
        # LGBM_OBS_HEALTH_PORT env var a parent process exports)
        self._health_every = int(
            getattr(config, "obs_health_check_iters", 0) or 0)
        server = obs_health.maybe_start(
            getattr(config, "obs_health_port", 0))
        self._health_enabled = bool(server is not None or self._health_every)
        if self._health_enabled and os.environ.get("LGBM_FLIGHT_DIR"):
            # a parent process named a directory for the dump: arm the flight
            # recorder so a divergence or kill leaves forensics even when
            # obs_telemetry is off
            from ..obs import flight as obs_flight
            obs_flight.install()
        self._health_jit = None
        # programs whose cost and device scopes are in the obs tables
        self._recorded_programs: set = set()
        self._drained_at = None         # (iteration, time_ns) of the last drain
        self._models: List[Tree] = []
        # deferred host trees: (tree_arrays, shrinkage, bias, iter,
        # health_stats-or-None, frontier_stats-or-None) tuples whose
        # device->host copies are in flight (see `models` property)
        self._pending: List[tuple] = []
        self._stop_flag = False
        self._empty_by_iter: Dict[int, int] = {}
        self.valid_sets: List[Dataset] = []
        self.valid_names: List[str] = []
        self.iter_ = 0
        self.num_class = config.num_class
        self.num_tree_per_iteration = 1
        self.max_feature_idx = 0
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.init_scores: List[float] = []
        self.shrinkage_rate = config.learning_rate
        self._train_score = None       # [K, N] device
        self._valid_scores: List = []
        self._eval_history: Dict[str, Dict[str, List[float]]] = {}
        self._early_stop_counter = 0
        self._best_iter: Dict[str, int] = {}
        self._prev_scores = None
        # the frontier grower's histogram store between trees (``_grow``),
        # keyed by shape; ``engine.cv`` gives its folds one pool
        self.hist_pool: Dict[tuple, jax.Array] = {}
        self._device_trees: List = []        # per-model device TreeArrays
        self._tree_weights: List[float] = []  # current scale of each model
        self.train_data_name = "training"    # Booster.set_train_data_name
        if train_data is not None:
            self.init_train(train_data)

    # ------------------------------------------------------------------
    # Deferred host-tree materialization.  Every synchronous device fetch
    # stalls the host until the device has caught up, so the fast
    # training path (no leaf renewal / linear trees / CEGB) keeps the whole
    # iteration on device, starts an async device->host copy of the tree
    # arrays, and only builds the host-side ``Tree`` when someone actually
    # reads ``self.models`` — by which time the copy has long landed.
    @property
    def models(self) -> List[Tree]:
        self._drain_pending()
        return self._models

    @models.setter
    def models(self, value: List[Tree]) -> None:
        self._pending.clear()
        self._models = value

    def _drain_pending(self, keep: int = 0) -> None:
        """Materialize pending device trees (oldest first), leaving at most
        ``keep`` in flight."""
        while len(self._pending) > keep:
            arrs, shrink, bias, _it, health_dev, stats_dev = \
                self._pending.pop(0)
            # the host waiting for a tree issued earlier (under update():
            # the tree before the one just issued); the span carries that
            # tree's frontier counters, which rode the same async copy
            tracer = get_tracer()
            tracer.begin("lgbm/update/drain", tree_iteration=_it)
            host = jax.device_get(arrs)
            tracer.end("lgbm/update/drain",
                       **(self._count_frontier(*stats_dev)
                          if stats_dev is not None else {}),
                       **self._count_leaves(host))
            self._observe_drain(_it)
            if health_dev is not None:
                # sentinel scalars rode the same async materialization —
                # by now they are computed+copied, so this is a cheap host
                # read, not a new device sync
                self._run_numeric_check(_it, health_dev)
            nl = int(host.num_leaves)
            if self._obs is not None:
                self._obs.tree_event(_it, num_leaves=nl, split_gains=[
                    float(v) for v in
                    np.asarray(host.split_gain)[:max(0, nl - 1)]])
            tree = Tree.from_arrays(host, self.train_data, learning_rate=1.0)
            tree.shrink(shrink)
            if bias:
                if nl > 1:
                    tree.add_bias(bias)
                else:
                    tree.leaf_value = np.full_like(tree.leaf_value, bias)
            self._models.append(tree)
            if nl <= 1:
                # when ALL trees of an iteration are split-less, report stop
                # on the next update (one iteration late vs the reference's
                # synchronous check, gbdt.cpp:375-388)
                cnt = self._empty_by_iter.get(_it, 0) + 1
                self._empty_by_iter[_it] = cnt
                if cnt >= self.num_tree_per_iteration:
                    self._stop_flag = True

    def _observe_drain(self, it: int) -> None:
        """Drain to drain is what the device took for one iteration's trees
        (the host runs ahead and waits here): the cost ledger's measured
        time for the grow program, which the enqueue's host time is not."""
        now = time.time_ns()
        last, self._drained_at = self._drained_at, (it, now)
        if self._obs is not None and last and last[0] == it - 1:
            obs_costs.get_ledger().observe(
                "train.grow_tree",
                (now - last[1]) / 1e9 / self.num_tree_per_iteration)

    @staticmethod
    def _count_frontier(stats_dev, rows: int) -> Dict[str, int]:
        """The grower's counters of one tree (``rows`` is what each frontier
        round passed) into ``obs.metrics``, totals as counters and per tree
        as histograms; returned for the drain span to carry.  The serial
        grower counts no rounds, and ``frontier_trees`` 0."""
        rounds, hi, lo, frontier, blocks = (int(v) for v in
                                            np.asarray(stats_dev))
        obs_metrics.counter("train.frontier_trees").inc(frontier)
        if not frontier:
            return {"frontier_trees": 0}
        counts = {"frontier_trees": 1, "rounds": rounds,
                  "rows_passed": rounds * rows,
                  "rows_selected": (hi << 20) + lo,
                  "hist_feature_blocks": blocks}
        for name, v in (("train.frontier_rounds", counts["rounds"]),
                        ("train.rows_passed", counts["rows_passed"]),
                        ("train.rows_selected", counts["rows_selected"]),
                        ("train.hist_feature_blocks", blocks)):
            obs_metrics.counter(name).inc(v)
            obs_metrics.histogram(name + "_per_tree").observe(v)
        return counts

    @staticmethod
    def _count_leaves(host) -> Dict[str, float]:
        """What one tree's leaves look like, from the arrays the drain
        already holds on the host (no device work): how many there are, how
        many hold under 100 rows, the smallest hessian sum and the deepest
        leaf, into ``obs.metrics`` and returned for the drain span to carry.
        A tree of small leaves is where a sum's error shows (frontier.py,
        "Sums"), and its depth is the rounds and the levels it costs."""
        nl = int(host.num_leaves)
        if nl <= 1:
            return {}
        depth = np.zeros(nl - 1, np.int64)      # a node's id is above its parent's
        for child in (np.asarray(host.left_child)[:nl - 1],
                      np.asarray(host.right_child)[:nl - 1]):
            inner = child >= 0
            depth[child[inner]] = np.flatnonzero(inner)
        for j in range(1, nl - 1):
            depth[j] = depth[depth[j]] + 1      # held the parent's id until now
        counts = {"leaves": nl,
                  "leaves_under_100_rows": int(np.sum(
                      np.asarray(host.leaf_count)[:nl] < 100)),
                  "tree_depth": int(depth.max()) + 1,
                  "min_leaf_hessian": float(np.min(
                      np.asarray(host.leaf_weight)[:nl]))}
        for name in ("leaves", "leaves_under_100_rows", "tree_depth"):
            obs_metrics.counter("train." + name).inc(counts[name])
        obs_metrics.histogram("train.min_leaf_hessian_per_tree").observe(
            counts["min_leaf_hessian"])
        return counts

    def _record_program(self, name: str, fn, *args, **meta) -> None:
        """Once per program and booster, right after its first call: the
        compiled program's cost into the obs ledger and its operations'
        scopes into ``obs.device_scopes()``.  jax hands back the trace and
        the executable it holds (no second compilation); never fatal."""
        if name in self._recorded_programs:
            return
        self._recorded_programs.add(name)
        obs_costs.record_program(name, fn, *args, **meta)

    # ------------------------------------------------------------------
    def init_train(self, train_data: Dataset) -> None:
        cfg = self.config
        self.train_data = train_data
        if self.objective is None:
            self.objective = create_objective(cfg)
        if self.objective is not None:
            self.objective.init(train_data.metadata, train_data.num_data)
            self.num_tree_per_iteration = self.objective.num_model_per_iteration
        else:
            self.num_tree_per_iteration = max(1, cfg.num_class)
        self.max_feature_idx = train_data.num_total_features - 1
        self.train_metrics = create_metrics(cfg)
        for m in self.train_metrics:
            m.init(train_data.metadata, train_data.num_data)
        tracer = get_tracer()
        with tracer.span("lgbm/booster/init/to_device", what="bins,labels"):
            self._dd = train_data.device_data()
            self._label_dev = (
                jnp.asarray(train_data.metadata.label)
                if train_data.metadata.label is not None else None)
            self._weight_dev = (
                jnp.asarray(train_data.metadata.weight)
                if train_data.metadata.weight is not None else None)
        K = self.num_tree_per_iteration
        n = train_data.num_data

        # boost from average / init_score (gbdt.cpp:338-368)
        init = np.zeros((K, n), dtype=np.float32)
        md_init = train_data.metadata.init_score
        self.init_scores = [0.0] * K
        if md_init is not None:
            init += md_init.reshape(-1, n).astype(np.float32)
        elif cfg.boost_from_average and self.objective is not None:
            for k in range(K):
                s = self.objective.boost_from_score(k)
                self.init_scores[k] = s
                init[k] += s
        with tracer.span("lgbm/booster/init/to_device", what="scores"):
            self._train_score = jnp.asarray(init)
        self._grower_cfg = self._make_grower_cfg()
        self._setup_parallel()
        gc = self._grower_cfg
        dev = jax.devices()[0]
        Log.info("training on platform=%s device_kind=%s devices=%d mesh=%d "
                 "hist_method=%s hist_variant=%s grower=%s",
                 dev.platform, dev.device_kind, jax.device_count(),
                 gc.num_shards if self._mesh is not None else 1,
                 gc.hist_method, gc.hist_variant, self._grower_name())

    def _grower_name(self) -> str:
        """'frontier' or 'serial': the grower ``grow_tree`` will trace for
        this configuration (its own gate, asked up front so the choice is
        in the log and not only in the compiled program)."""
        from ..ops.grower import _frontier_eligible
        coupled, lazy = self._cegb_vectors()
        ok = _frontier_eligible(self._grower_cfg, self._interaction_sets(),
                                coupled, lazy, self._forced_splits(),
                                self._dd.efb)
        return "frontier" if ok else "serial"

    def _setup_parallel(self) -> None:
        """Route ``tree_learner=data|feature|voting`` through a device mesh
        (the analog of the reference's learner×device ``CreateTreeLearner``
        factory, ``tree_learner.cpp:15-53``).  Falls back to serial with a
        warning when only one device is available."""
        from ..parallel.mesh import DATA_AXIS, FEATURE_AXIS, default_mesh
        cfg = self.config
        self._mesh = None
        tl = cfg.tree_learner or "serial"
        if tl == "serial":
            return
        n_dev = cfg.mesh_shape[0] if cfg.mesh_shape else len(jax.devices())
        if n_dev < 2:
            Log.warning(
                "tree_learner=%s requested but only one device is available; "
                "training serially", tl)
            return
        if tl in ("feature", "voting") and self._dd.efb is not None:
            # the Dataset disables bundling when its params request these
            # learners; a dataset constructed for serial/data training and
            # then reused here would silently misalign per-feature metadata
            # against bundle columns
            raise LightGBMError(
                f"tree_learner={tl} cannot train on an EFB-bundled Dataset; "
                "construct the Dataset with tree_learner=%s or "
                "enable_bundle=false in its params" % tl)
        axis = FEATURE_AXIS if tl == "feature" else DATA_AXIS
        self._mesh = default_mesh(n_dev, axis_name=axis)
        self._grower_cfg = self._grower_cfg._replace(
            axis_name=axis, parallel_mode=tl, num_shards=n_dev,
            top_k=cfg.top_k)
        if axis == DATA_AXIS and self.train_data.num_data % n_dev == 0:
            # Put the row-sharded operands on the mesh ONCE.  Left where
            # jnp.asarray made them (device 0), every tree's shard_map call
            # re-scatters the whole bin matrix, and the second tree
            # recompiles the grow program: the score comes back from the
            # first step row-sharded, so the gradients change sharding
            # between the first call and the rest (chip_smoke.py --devices
            # prints the placements).  Rows that do not divide the mesh are
            # padded inside the jitted step and stay as they were.
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._dd = dataclasses.replace(self._dd, bins=jax.device_put(
                self._dd.bins, NamedSharding(self._mesh, P(axis))))
            self._train_score = jax.device_put(
                self._train_score, NamedSharding(self._mesh, P(None, axis)))

    def _make_grower_cfg(self) -> GrowerConfig:
        cfg = self.config
        max_bin = int(max((self.train_data.num_bin(i)
                           for i in range(self.train_data.num_features)), default=2))
        # round up to a TPU-friendly lane width
        max_bin = max(4, min(cfg.max_bin + 1, -(-max_bin // 4) * 4))
        sp = SplitParams(
            lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
            min_data_in_leaf=cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=cfg.min_gain_to_split,
            max_delta_step=cfg.max_delta_step,
            path_smooth=cfg.path_smooth,
            cat_smooth=cfg.cat_smooth, cat_l2=cfg.cat_l2,
            max_cat_to_onehot=cfg.max_cat_to_onehot,
            max_cat_threshold=cfg.max_cat_threshold,
            min_data_per_group=cfg.min_data_per_group)
        # static: does any feature take the sorted many-category scan?
        # (num_bin > max_cat_to_onehot categorical, feature_histogram.hpp:316)
        ds = self.train_data
        from ..io.bin import BinType
        sorted_cat = any(
            ds.bin_mappers[r].bin_type == BinType.CATEGORICAL
            and ds.num_bin(i) > cfg.max_cat_to_onehot
            for i, r in enumerate(ds.used_features))
        # histogram layout: auto-picked by backend (the analog of the
        # reference's TrainingShareStates timed row/col-wise autotune,
        # train_share_states.h — here the winner per backend is known:
        # pallas one-hot on TPU, scatter-add on CPU, so the pick is static
        # and the first-iteration timing run is saved); force_col_wise/
        # force_row_wise override it like the reference's flags
        # (col-wise = per-column scatter adds, row-wise = each row pushed
        # into all feature histograms at once = the one-hot matmul)
        if cfg.force_col_wise:
            hist_method = "scatter"
        elif cfg.force_row_wise:
            hist_method = ("pallas" if jax.default_backend() == "tpu"
                           else "onehot")
        else:
            hist_method = {"tpu": "pallas", "cpu": "scatter"}.get(
                jax.default_backend(), "onehot")
        if cfg.force_col_wise and jax.default_backend() == "tpu":
            Log.warning("force_col_wise maps to the scatter histogram "
                        "kernel, which is much slower than the default "
                        "one-hot MXU kernel on TPU")
        # one-hot build strategy for the pallas kernels: 'auto' runs the
        # one-time cached on-device micro-bench (ops/onehot_variants.pick_
        # variant — the reference train_share_states auto-tuner's TPU
        # analog); an explicit name is validated against the KERNEL bin
        # width (the EFB bundle width when bundling is on).  Resolved to a
        # concrete static string HERE, before GrowerConfig exists, so the
        # compiled tree program never retraces over it.
        if hist_method == "pallas":
            from ..ops import onehot_variants as _ov
            kernel_bins = self._dd.bundle_bins or max_bin
            if cfg.hist_variant == "auto":
                tracer = get_tracer()
                tracer.begin("lgbm/booster/init/election")
                hist_variant = _ov.pick_variant(
                    kernel_bins, self.train_data.num_features)
                tracer.end("lgbm/booster/init/election", variant=hist_variant)
            else:
                hist_variant = _ov.resolve(cfg.hist_variant, kernel_bins)
        else:
            hist_variant = "base"           # XLA fallbacks ignore it
        return GrowerConfig(
            num_leaves=cfg.num_leaves, max_depth=cfg.max_depth, max_bin=max_bin,
            split=sp, feature_fraction_bynode=cfg.feature_fraction_bynode,
            hist_method=hist_method, hist_variant=hist_variant,
            hist_chunk_rows=cfg.hist_chunk_rows,
            cegb_split_penalty=cfg.cegb_tradeoff * cfg.cegb_penalty_split,
            hist_compact=cfg.hist_compact,
            hist_compact_min_cap=cfg.hist_compact_min_cap,
            hist_compact_ladder=cfg.hist_compact_ladder,
            extra_trees=cfg.extra_trees,
            extra_seed=cfg.extra_seed,
            sorted_cat=sorted_cat,
            bundle_bins=self._dd.bundle_bins,
            monotone_penalty=cfg.monotone_penalty,
            monotone_mode=cfg.monotone_constraints_method,
            has_monotone=any(v != 0 for v in cfg.monotone_constraints),
            grower_mode=cfg.tree_grower,
            frontier_k=cfg.frontier_k,
            frontier_block_rows=cfg.frontier_block_rows)

    # ------------------------------------------------------------------
    # feature-gating state: interaction constraints + CEGB (SURVEY.md §2.4)
    def _interaction_sets(self):
        """[C, F_inner] 0/1 matrix of interaction-constraint groups over inner
        feature ids, or None (``col_sampler.hpp:74``)."""
        groups = self.config.interaction_constraints
        if not groups:
            return None
        used = list(self.train_data.used_features)
        real2inner = {r: i for i, r in enumerate(used)}
        mat = np.zeros((len(groups), len(used)), np.float32)
        for c, grp in enumerate(groups):
            for real in grp:
                if real in real2inner:
                    mat[c, real2inner[real]] = 1.0
        return jnp.asarray(mat)

    def _forced_splits(self):
        """Parse ``forcedsplits_filename`` into the grower's static BFS tuple
        (side, inner_feature, threshold_bin, parent_forced_idx); the grower
        resolves target leaf ids at runtime (a forced split that fails its
        gates must not shift its siblings' numbering)."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return ()
        import json
        with open(fname) as fh:
            root = json.load(fh)
        ds = self.train_data
        real2inner = {r: i for i, r in enumerate(ds.used_features)}
        out = []
        queue = [(root, 0, -1)]
        while queue and len(out) < self.config.num_leaves - 1:
            node, side, par = queue.pop(0)
            if not node:
                continue
            real_f = int(node["feature"])
            if real_f not in real2inner:
                Log.warning("forced split on unused feature %d ignored", real_f)
                continue
            mapper = ds.bin_mappers[real_f]
            thr_bin = int(np.asarray(
                mapper.value_to_bin(np.array([float(node["threshold"])])))[0])
            idx = len(out)
            out.append((side, real2inner[real_f], thr_bin, par))
            if node.get("left"):
                queue.append((node["left"], 0, idx))
            if node.get("right"):
                queue.append((node["right"], 1, idx))
        return tuple(out)

    # ------------------------------------------------------------------
    # linear trees (linear_tree=true; LinearTreeLearner, SURVEY.md §2.4)
    @functools.cached_property
    def _raw_dev(self):
        if self.train_data.raw_data is None:
            raise LightGBMError(
                "linear_tree=true requires the Dataset to keep raw values; "
                "pass linear_tree in the Dataset params")
        return jnp.asarray(self.train_data.raw_data)

    def _branch_features(self, tree) -> list:
        """Per-leaf sorted unique NUMERICAL real feature ids on the
        root->leaf path (linear_tree_learner.cpp:195-215)."""
        from ..io.bin import BinType
        mappers = self.train_data.bin_mappers
        paths = [[] for _ in range(tree.num_leaves)]
        stack = [(0, [])]
        while stack:
            node, fs = stack.pop()
            if node < 0:
                paths[~node] = sorted({
                    f for f in fs
                    if mappers[f].bin_type != BinType.CATEGORICAL})
                continue
            fs2 = fs + [int(tree.split_feature[node])]
            stack.append((int(tree.left_child[node]), fs2))
            stack.append((int(tree.right_child[node]), fs2))
        return paths

    def _fit_linear_tree(self, tree, node_assign, g, h,
                         row_weight, is_first_tree: bool):
        """Fit per-leaf linear models and return device arrays for the score
        update, or None when constants suffice (first tree)."""
        nl = tree.num_leaves
        tree.is_linear = True
        if is_first_tree:
            # first tree: constants only (linear_tree_learner.cpp:175-181)
            tree.leaf_const = np.asarray(tree.leaf_value, np.float64).copy()
            tree.leaf_coeff = [[] for _ in range(nl)]
            tree.leaf_features = [[] for _ in range(nl)]
            return None
        paths = self._branch_features(tree)
        L = self._grower_cfg.num_leaves
        k_raw = max(1, max((len(p) for p in paths), default=1))
        K = 1 << (k_raw - 1).bit_length()          # pad: fewer recompiles
        feat_mat = np.full((L, K), -1, np.int32)
        for i, p in enumerate(paths):
            feat_mat[i, :len(p)] = p
        feat_dev = jnp.asarray(feat_mat)
        coeffs, consts, oks = self._fit_linear_jit(
            self._raw_dev, g, h, node_assign, row_weight, feat_dev)
        coeffs = np.asarray(coeffs, np.float64)
        consts = np.asarray(consts, np.float64)
        oks = np.asarray(oks)
        leaf_value = np.asarray(tree.leaf_value, np.float64)
        tree.leaf_const = np.where(oks[:nl], consts[:nl], leaf_value[:nl])
        tree.leaf_coeff, tree.leaf_features = [], []
        for i in range(nl):
            cs, fs = [], []
            if oks[i]:
                for jx, f in enumerate(paths[i]):
                    c = coeffs[i, jx]
                    if abs(c) > 1e-35:            # kZeroThreshold prune
                        cs.append(float(c))
                        fs.append(int(f))
            tree.leaf_coeff.append(cs)
            tree.leaf_features.append(fs)
        # device views for the score update: failed leaves behave as constants
        coeff_dev = jnp.asarray(np.where(oks[:, None], coeffs, 0.0), jnp.float32)
        const_dev = jnp.zeros(L, jnp.float32).at[:nl].set(
            jnp.asarray(tree.leaf_const, jnp.float32))
        return coeff_dev, const_dev, feat_dev

    def _valid_raw_dev(self, vi: int):
        if not hasattr(self, "_vraw_cache"):
            self._vraw_cache = {}
        if vi not in self._vraw_cache:
            vset = self.valid_sets[vi]
            if vset.raw_data is None:
                raise LightGBMError(
                    "linear_tree validation sets must keep raw values")
            self._vraw_cache[vi] = jnp.asarray(vset.raw_data)
        return self._vraw_cache[vi]

    @functools.cached_property
    def _fit_linear_jit(self):
        from ..ops.linear import fit_leaf_linear
        lam = self.config.linear_lambda
        L = self._grower_cfg.num_leaves

        @jax.jit    # retraces per feat_mat width K (power-of-2 padded)
        def fn(raw, g, h, na, rw, feat_mat):
            return fit_leaf_linear(raw, g, h, na, rw, feat_mat, L, lam)
        return fn

    def _feature_contri_vec(self):
        """[F_inner] per-feature gain multipliers (reference
        feature_contri -> FeatureMetainfo::penalty), or None."""
        fc = self.config.feature_contri
        if not fc:
            return None
        used = list(self.train_data.used_features)
        if len(fc) != self.train_data.num_total_features:
            raise LightGBMError(
                "feature_contri should be the same size as feature number")
        return jnp.asarray([fc[r] for r in used], jnp.float32)

    def _cegb_vectors(self):
        """(coupled[F_inner]|None, lazy[F_inner]|None), tradeoff-premultiplied."""
        cfg = self.config
        used = list(self.train_data.used_features)

        def vec(pen):
            if not pen:
                return None
            if len(pen) < self.train_data.num_total_features:
                raise LightGBMError(
                    "cegb_penalty_feature_* should be the same size as feature number")
            return jnp.asarray([cfg.cegb_tradeoff * pen[r] for r in used],
                               jnp.float32)
        return vec(cfg.cegb_penalty_feature_coupled), vec(cfg.cegb_penalty_feature_lazy)

    def add_valid_data(self, valid_data: Dataset, name: str) -> None:
        check(valid_data.reference is self.train_data or
              valid_data.bin_mappers is self.train_data.bin_mappers,
              "validation set must be constructed with reference=train_set")
        self.valid_sets.append(valid_data)
        self.valid_names.append(name)
        metrics = create_metrics(self.config)
        for m in metrics:
            m.init(valid_data.metadata, valid_data.num_data)
        if not hasattr(self, "valid_metrics"):
            self.valid_metrics = []
        self.valid_metrics.append(metrics)
        K = self.num_tree_per_iteration
        n = valid_data.num_data
        init = np.zeros((K, n), dtype=np.float32)
        md_init = valid_data.metadata.init_score
        if md_init is not None:
            init += md_init.reshape(-1, n).astype(np.float32)
        else:
            for k in range(K):
                init[k] += self.init_scores[k]
        with get_tracer().span("lgbm/booster/init/to_device",
                               what="valid bins,scores"):
            valid_data.device_data()
            self._valid_scores.append(jnp.asarray(init))

    # ------------------------------------------------------------------
    # bagging (gbdt.cpp:182-262); subclasses (GOSS) override
    def _bagging_weights(self, iteration: int, grad, hess):
        cfg = self.config
        n = self.train_data.num_data
        need = cfg.bagging_freq > 0 and (cfg.bagging_fraction < 1.0 or
                                         cfg.pos_bagging_fraction < 1.0 or
                                         cfg.neg_bagging_fraction < 1.0)
        if not need:
            return None, grad, hess
        if iteration % cfg.bagging_freq == 0:
            key = key_for_iteration(cfg.bagging_seed, iteration // cfg.bagging_freq)
            self._bag_mask = self._sample_jit(key, self._label_dev)
            self._record_program("train.sample", self._sample_jit, key,
                                 self._label_dev)
        mask = self._bag_mask
        return mask, grad * mask, hess * mask

    @functools.cached_property
    def _sample_jit(self):
        cfg = self.config
        n = self.train_data.num_data

        @jax.jit
        @jax.named_scope("lgbm/sample")
        def bag_sample(key, label):
            return bag_mask_from_uniform(cfg, jax.random.uniform(key, (n,)),
                                         label)
        return bag_sample

    # -- bagging subset (reference CopySubrow, gbdt.cpp:256): when bagging
    # drops a material fraction of rows, compact the survivors into a
    # fixed-capacity buffer so every grower pass costs O(cap), not O(N).
    # The MASK still decides membership (identical trees to the masked
    # path — the compaction is exact as long as count <= cap, and cap
    # carries a >6-sigma margin over the Bernoulli mean), so serial,
    # data-parallel and masked runs stay in exact parity.
    _BAG_SUBSET_MAX_FRACTION = 0.8

    def _bag_subset_capacity(self) -> Optional[int]:
        cfg = self.config
        n = self.train_data.num_data
        if (cfg.bagging_freq <= 0 or not (0.0 < cfg.bagging_fraction
                                          < self._BAG_SUBSET_MAX_FRACTION)
                or cfg.pos_bagging_fraction < 1.0
                or cfg.neg_bagging_fraction < 1.0
                or getattr(self, "_mesh", None) is not None
                or type(self)._bagging_weights is not GBDT._bagging_weights):
            return None
        return self._capacity_with_margin(n * cfg.bagging_fraction, n)

    @staticmethod
    def _capacity_with_margin(expected_k: float, n: int) -> Optional[int]:
        """Bag buffer capacity: expected count + a >6-sigma Bernoulli
        margin, rounded up to 1024; None when it wouldn't beat full width.
        Shared by every booster that compacts its bag (GBDT, GOSS)."""
        cap = int(expected_k + max(64.0, 6.0 * float(np.sqrt(max(1.0, expected_k)))))
        cap = -(-cap // 1024) * 1024
        return cap if cap < n else None

    def _bag_subset_refresh(self, iteration: int) -> bool:
        """True when the bag membership changed this iteration (subclasses
        that re-bag every iteration override)."""
        return iteration % self.config.bagging_freq == 0

    @functools.cached_property
    def _bag_compact_jit(self):
        from ..ops.histogram import unrolled_rank
        n = self.train_data.num_data

        @functools.partial(jax.jit, static_argnums=2)
        @jax.named_scope("lgbm/sample")
        def fn(mask, bins, cap):
            cs = jnp.cumsum((mask > 0).astype(jnp.int32))
            targets = jnp.arange(1, cap + 1, dtype=jnp.int32)
            row_ids = jnp.minimum(unrolled_rank(cs, targets, strict=True),
                                  n - 1)
            filled = targets <= cs[-1]
            rw = jnp.where(filled, jnp.take(mask, row_ids), 0.0)
            return row_ids, rw, jnp.take(bins, row_ids, axis=0)
        return fn

    def _feature_mask(self, iteration: int) -> jnp.ndarray:
        cfg = self.config
        f = self.train_data.num_features
        if cfg.feature_fraction >= 1.0:
            return jnp.ones(f, jnp.float32)
        # per-tree column sampling (ColSampler::ResetByTree, col_sampler.hpp:74)
        rng = np.random.default_rng(cfg.feature_fraction_seed + iteration)
        k = max(1, int(round(cfg.feature_fraction * f)))
        mask = np.zeros(f, np.float32)
        mask[rng.choice(f, size=k, replace=False)] = 1.0
        return jnp.asarray(mask)

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference ``GBDT::TrainOneIter``,
        ``gbdt.cpp:369``).  Returns True if training should stop (no splits)."""
        cfg = self.config
        K = self.num_tree_per_iteration
        n = self.train_data.num_data
        it = self.iter_

        if self._stop_flag:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            return True

        # host spans (always recorded, a dozen a tree): what the host spent
        # ISSUING each step of an asynchronous program and, under
        # lgbm/update/drain, waiting for the device; the device's own time by
        # phase is in a profiler trace, read through obs.device_scopes()
        obs = self._obs
        tracer = get_tracer()
        tracer.begin("lgbm/update", iteration=it)
        try:
            should_stop = self._train_one_iter(grad, hess, it, tracer)
        finally:
            tracer.end("lgbm/update")
        if obs is not None:
            obs.iteration_event(it, trees=K)
        elif self._health_enabled:
            obs_health.set_status(stage="train", iteration=it)
        return should_stop

    def _train_one_iter(self, grad, hess, it: int, tracer) -> bool:
        cfg = self.config
        K = self.num_tree_per_iteration
        n = self.train_data.num_data
        obs = self._obs
        with tracer.span("lgbm/update/gradients"):
            if grad is None or hess is None:
                g, h = self._compute_gradients(self._train_score)
            else:
                g = jnp.asarray(np.asarray(grad, np.float32).reshape(K, n))
                h = jnp.asarray(np.asarray(hess, np.float32).reshape(K, n))

        with tracer.span("lgbm/update/sample"):
            bag_mask, g, h = self._bagging_weights(it, g, h)
        row_weight = bag_mask if bag_mask is not None else jnp.ones(n, jnp.float32)
        fmask = self._feature_mask(it)
        self._prev_scores = (self._train_score, list(self._valid_scores))

        cegb_coupled0, cegb_used0 = self._cegb_state()
        _, cegb_lazy0 = self._cegb_vectors()
        fast = ((self.objective is None
                 or not self.objective.need_renew_tree_output())
                and not cfg.linear_tree
                and cegb_coupled0 is None and cegb_lazy0 is None)
        if fast:
            return self._train_one_iter_fast(g, h, row_weight, fmask, it, K,
                                             bag_mask=bag_mask)

        should_stop = True
        for k in range(K):
            with tracer.span("lgbm/update/grow_dispatch"):
                cegb_coupled, cegb_used = self._cegb_state()
                grow_args = (self._dd.bins, g[k], h[k], row_weight, fmask,
                             key_for_iteration(cfg.seed, it, salt=k + 1),
                             cegb_coupled, cegb_used)
                tree_arrays, node_assign, stats_dev = self._grow(grow_args)
            self._record_grow_program(grow_args)
            # ONE host fetch for the whole tree, not one blocking
            # np.asarray per field
            tracer.begin("lgbm/update/drain", tree_iteration=it)
            tree_host = jax.device_get(tree_arrays)
            tracer.end("lgbm/update/drain",
                       **self._count_frontier(stats_dev, n),
                       **self._count_leaves(tree_host))
            if self._health_due(it, k):
                # the slow path already syncs per tree; check in line
                self._run_numeric_check(it, self._health_stats_fn()(
                    g[k], h[k], tree_arrays.leaf_value))
            self._cegb_update(tree_host, node_assign, bag_mask)
            nl = int(tree_host.num_leaves)
            if obs is not None:
                obs.tree_event(it, num_leaves=nl, split_gains=[
                    float(v) for v in
                    np.asarray(tree_host.split_gain)[:max(0, nl - 1)]])
            if nl > 1:
                should_stop = False
            tree = Tree.from_arrays(tree_host, self.train_data, learning_rate=1.0)

            # leaf renewal for L1-style objectives (RenewTreeOutput,
            # serial_tree_learner.cpp:684)
            if self.objective is not None and self.objective.need_renew_tree_output() and nl > 1:
                leaf_pred = np.asarray(node_assign)
                score_host = np.asarray(self._train_score[k], np.float64)
                new_vals = self.objective.renew_leaf_values(
                    leaf_pred, score_host, tree.leaf_value.copy(), nl)
                tree.leaf_value = np.asarray(new_vals, np.float64)
                tree_arrays = tree_arrays._replace(
                    leaf_value=jnp.asarray(tree.leaf_value, jnp.float32))

            linear_dev = None
            if cfg.linear_tree and nl > 1:
                linear_dev = self._fit_linear_tree(
                    tree, node_assign, g[k], h[k], row_weight,
                    is_first_tree=(it == 0))
            elif cfg.linear_tree:
                tree.is_linear = True
                tree.leaf_const = np.asarray(tree.leaf_value, np.float64).copy()
                tree.leaf_coeff = [[] for _ in range(max(1, nl))]
                tree.leaf_features = [[] for _ in range(max(1, nl))]

            tree.shrink(self.shrinkage_rate)
            # first tree carries the boost-from-average bias (Tree::AddBias);
            # a split-less first tree becomes a constant tree holding the bias
            if it == 0 and self.init_scores[k] != 0.0:
                if nl > 1:
                    tree.add_bias(self.init_scores[k])
                else:
                    tree.leaf_value = np.full_like(tree.leaf_value, self.init_scores[k])
                    if tree.is_linear:
                        tree.leaf_const = np.asarray(tree.leaf_value, np.float64).copy()

            with tracer.span("lgbm/update/score_dispatch"):
                delta = tree_arrays.leaf_value * self.shrinkage_rate
                if linear_dev is not None:
                    from ..ops.linear import linear_leaf_delta
                    coeff_dev, const_dev, feat_dev = linear_dev
                    row_delta = linear_leaf_delta(
                        self._raw_dev, node_assign, coeff_dev, const_dev,
                        feat_dev, tree_arrays.leaf_value) * self.shrinkage_rate
                    self._train_score = self._train_score.at[k].add(row_delta)
                else:
                    self._train_score = self._train_score.at[k].add(
                        jnp.where(nl > 1, delta[node_assign], 0.0))
                for vi, vset in enumerate(self.valid_sets):
                    vleaf = self._predict_leaf_jit(tree_arrays, vset.device_data().bins)
                    if linear_dev is not None:
                        vraw = self._valid_raw_dev(vi)
                        vdelta = linear_leaf_delta(
                            vraw, vleaf, coeff_dev, const_dev, feat_dev,
                            tree_arrays.leaf_value) * self.shrinkage_rate
                        self._valid_scores[vi] = self._valid_scores[vi].at[k].add(vdelta)
                    else:
                        self._valid_scores[vi] = self._valid_scores[vi].at[k].add(
                            jnp.where(nl > 1, delta[vleaf], 0.0))
            self.models.append(tree)
            self._device_trees.append(tree_arrays)
            self._tree_weights.append(self.shrinkage_rate)

        self.iter_ += 1
        if should_stop:
            Log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
        return should_stop

    def _train_one_iter_fast(self, g, h, row_weight, fmask, it: int,
                             K: int, bag_mask=None) -> bool:
        """Device-resident iteration: grow, score-update and valid-update all
        stay on device; the host tree materializes lazily (``models``
        property), so the boosting loop issues work without ever blocking on
        the device — the per-tree host round-trip of the synchronous path
        disappears from the critical path."""
        cfg = self.config
        cap = self._bag_subset_capacity() if bag_mask is not None else None
        if cap is not None:
            if (self._bag_subset_refresh(it)
                    or getattr(self, "_bag_sub", None) is None):
                self._bag_sub = self._bag_compact_jit(bag_mask, self._dd.bins,
                                                      cap)
                self._record_program("train.sample_compact",
                                     self._bag_compact_jit, bag_mask,
                                     self._dd.bins, cap)
            bag_rows, bag_rw, bag_bins = self._bag_sub
        tracer = get_tracer()
        shrink = self.shrinkage_rate
        for k in range(K):
            with tracer.span("lgbm/update/grow_dispatch"):
                key = key_for_iteration(cfg.seed, it, salt=k + 1)
                if cap is not None:
                    # grow over the compacted bag; leaf assignment for the
                    # FULL training set comes from one binned traversal
                    grow_args = (bag_bins, jnp.take(g[k], bag_rows),
                                 jnp.take(h[k], bag_rows), bag_rw, fmask,
                                 key, None, None)
                    tree_arrays, _, stats_dev = self._grow(grow_args)
                    node_assign = self._predict_leaf_jit(tree_arrays,
                                                         self._dd.bins)
                    self._record_program("train.bag_traverse",
                                         self._predict_leaf_jit, tree_arrays,
                                         self._dd.bins)
                else:
                    grow_args = (self._dd.bins, g[k], h[k], row_weight,
                                 fmask, key, None, None)
                    tree_arrays, node_assign, stats_dev = self._grow(
                        grow_args)
            self._record_grow_program(grow_args)
            jax.tree.map(lambda a: a.copy_to_host_async(),
                         (tree_arrays, stats_dev))
            health_dev = None
            if self._health_due(it, k):
                # sentinel reductions ride the same async materialization:
                # dispatched now, judged at drain time — no new device sync
                health_dev = self._health_stats_fn()(
                    g[k], h[k], tree_arrays.leaf_value)
                jax.tree.map(lambda a: a.copy_to_host_async(), health_dev)
            bias = (self.init_scores[k]
                    if it == 0 and self.init_scores[k] != 0.0 else 0.0)
            self._pending.append((tree_arrays, shrink, bias, it, health_dev,
                                  (stats_dev, int(grow_args[0].shape[0]))))
            with tracer.span("lgbm/update/score_dispatch"):
                # the product apart from the sum, as the serial path has it:
                # in one program the compiler may round them once (an FMA)
                delta = tree_arrays.leaf_value * shrink
                score_args = (self._train_score, delta,
                              tree_arrays.num_leaves, node_assign, k)
                self._train_score = self._score_update_jit(*score_args)
                self._record_program("train.score_update",
                                     self._score_update_jit, *score_args)
                for vi, vset in enumerate(self.valid_sets):
                    valid_args = (self._valid_scores[vi], tree_arrays, delta,
                                  vset.device_data().bins, k)
                    self._valid_scores[vi] = self._valid_update_jit(
                        *valid_args)
                    self._record_program(f"train.valid_update.{vi}",
                                         self._valid_update_jit, *valid_args)
            self._device_trees.append(tree_arrays)
            self._tree_weights.append(shrink)
        self.iter_ += 1
        # per-tree split-gain events come from _drain_pending when the async
        # host copies land: telemetry must not add a device sync here.
        # keep one iteration in flight: draining then blocks only on the
        # PREVIOUS iteration's device work (host stays a full iteration
        # ahead) and its async device->host copy has typically landed, so
        # the device_get is a cache read, not a round-trip.  The stop check
        # is therefore one iteration late (at most K extra constant trees).
        self._drain_pending(keep=K)
        return self._stop_flag

    # ------------------------------------------------------------------
    # numeric health sentinels (obs_health_check_iters): tiny device-side
    # isfinite/max-abs reductions over gradients, hessians and leaf values
    def _health_stats_fn(self):
        if self._health_jit is None:
            @jax.jit
            def stats(g, h, leaf):
                def s(x):
                    xf = jnp.asarray(x, jnp.float32).ravel()
                    finite = jnp.isfinite(xf)
                    return jnp.stack([
                        jnp.mean(finite.astype(jnp.float32)),
                        jnp.max(jnp.where(finite, jnp.abs(xf), 0.0))])
                return s(g), s(h), s(leaf)
            self._health_jit = stats
        return self._health_jit

    def _health_due(self, it: int, k: int) -> bool:
        """Sample one tree (k==0) every ``obs_health_check_iters`` rounds."""
        return bool(self._health_every and k == 0
                    and it % self._health_every == 0)

    def _run_numeric_check(self, it: int, health_dev) -> None:
        """Judge fetched sentinel scalars; raises DivergenceError on
        NaN/Inf (with a flight dump) via ``obs.health.check_numeric``."""
        g_s, h_s, l_s = jax.device_get(health_dev)
        stats = {
            "grad": {"finite_frac": float(g_s[0]),
                     "max_abs": float(g_s[1])},
            "hess": {"finite_frac": float(h_s[0]),
                     "max_abs": float(h_s[1])},
            "leaf_value": {"finite_frac": float(l_s[0]),
                           "max_abs": float(l_s[1])},
        }
        obs_health.check_numeric(
            stats, iteration=it, kind="train",
            log=self._obs.log if self._obs is not None else None)

    def _compute_gradients(self, score):
        obj = self.objective
        if obj is None:
            raise LightGBMError("objective is None; provide custom grad/hess")
        if self.num_tree_per_iteration > 1:
            return obj.get_gradients_multi(score, self._label_dev, self._weight_dev)
        if obj.pure_gradients:
            # one compiled program, so its operations carry lgbm/gradients
            args = (score, self._label_dev, self._weight_dev)
            out = self._gradients_jit(*args)
            self._record_program("train.gradients", self._gradients_jit,
                                 *args)
            return out
        g, h = obj.get_gradients(score[0], self._label_dev, self._weight_dev)
        return g[None, :], h[None, :]

    @functools.cached_property
    def _gradients_jit(self):
        obj = self.objective

        @jax.jit
        @jax.named_scope("lgbm/gradients")
        def gradients(score, label, weight):
            g, h = obj.get_gradients(score[0], label, weight)
            return g[None, :], h[None, :]
        return gradients

    def _grow(self, grow_args):
        """``(tree, node_assign, stats)`` of the grow program on
        ``grow_args``.  Where the frontier grower grows on one device it
        grows in a histogram store (``frontier.hist_store_shape``: 3.5 GB at
        2,000 columns and 255 leaves) taken from ``hist_pool``, donated to
        the tree's program and handed back to the pool, so that training
        holds one store from tree to tree, as the reference allocates its
        ``HistogramPool`` once per learner.  The folds of ``engine.cv`` share
        one pool, so they hold one store between them; ``engine.train`` and
        ``engine.cv`` empty it when training ends (``release_hist_pool``).
        A store of another shape (``reset_parameter``) is dropped."""
        if getattr(self, "_mesh", None) is not None \
                or self._grower_name() != "frontier":
            return self._grow_jit(*grow_args, None)
        from ..ops.frontier import hist_store_shape
        shape = hist_store_shape(self._grower_cfg, int(grow_args[0].shape[1]))
        store = self.hist_pool.pop(shape, None)
        self.hist_pool.clear()
        if store is None:
            store = jnp.zeros(shape, jnp.float32)
        *out, self.hist_pool[shape] = self._grow_jit(*grow_args, store)
        return tuple(out)

    def release_hist_pool(self) -> None:
        """Free the histogram store kept between trees (``_grow``): training
        has ended.  A later ``update()`` makes a new one."""
        self.hist_pool.clear()

    def _record_grow_program(self, args) -> None:
        bins = args[0]
        self._record_program("train.grow_tree", self._grow_jit, *args,
                             next(iter(self.hist_pool.values()), None),
                             rows=int(bins.shape[0]),
                             features=int(bins.shape[1]))

    @functools.cached_property
    def _score_update_jit(self):
        """``score[k] += delta[node_assign]`` (``delta``: the shrunk leaf
        values), nothing for a tree that did not split (its one leaf holds
        no value)."""
        @functools.partial(jax.jit, static_argnums=4)
        @jax.named_scope("lgbm/score_update")
        def score_update(score, delta, num_leaves, node_assign, k):
            return score.at[k].add(
                jnp.where(num_leaves > 1, delta[node_assign], 0.0))
        return score_update

    @functools.cached_property
    def _valid_update_jit(self):
        """One validation set's rows down the new tree (binned traversal) and
        that set's score update."""
        dd = self._dd

        @functools.partial(jax.jit, static_argnums=4)
        @jax.named_scope("lgbm/valid_traverse")
        def valid_update(score, tree_arrays, delta, bins, k):
            leaf = predict_leaf_binned(tree_arrays, bins, dd.nan_bins,
                                       efb=dd.efb)
            return score.at[k].add(
                jnp.where(tree_arrays.num_leaves > 1, delta[leaf], 0.0))
        return valid_update

    @functools.cached_property
    def _grow_jit(self):
        dd = self._dd
        cfg = self._grower_cfg
        inter = self._interaction_sets()
        _, lazy = self._cegb_vectors()
        forced = self._forced_splits()
        contri = self._feature_contri_vec()
        mesh = getattr(self, "_mesh", None)

        if mesh is None:
            # the histogram store (GBDT._grow) is donated: the program
            # grows in it and hands it back, no second buffer
            @functools.partial(jax.jit, donate_argnums=8)
            def grow_tree_step(bins, g, h, rw, fmask, key, cegb_coupled,
                               cegb_used, hist_store):
                return grow_tree(bins, g, h, rw, fmask, dd.num_bins,
                                 dd.default_bins, dd.nan_bins,
                                 dd.is_categorical, dd.monotone, key, cfg,
                                 interaction_sets=inter,
                                 cegb_coupled=cegb_coupled,
                                 cegb_lazy=lazy, cegb_used_data=cegb_used,
                                 forced=forced, efb=dd.efb,
                                 feature_contri=contri, with_stats=True,
                                 hist_store=hist_store)
            return grow_tree_step

        # parallel learners: the same grow_tree program under shard_map, with
        # rows (data/voting) or features (feature) sharded over the mesh and
        # the grower's psum/pmax collectives joining the shards (reference
        # learner dataflows: data_parallel_tree_learner.cpp:155-251,
        # feature_parallel_tree_learner.cpp:38-57,
        # voting_parallel_tree_learner.cpp:151-345)
        from jax.sharding import PartitionSpec as P
        axis = cfg.axis_name
        ns = cfg.num_shards
        n = self.train_data.num_data
        f = self.train_data.num_features

        if cfg.parallel_mode == "feature":
            f_pad = (-f) % ns
            pad_i = lambda a, v: jnp.pad(a, (0, f_pad), constant_values=v)
            num_bins = pad_i(dd.num_bins, 1)
            default_bins = pad_i(dd.default_bins, 0)
            nan_bins = pad_i(dd.nan_bins, -1)
            is_cat = pad_i(dd.is_categorical, False)
            mono = pad_i(dd.monotone, 0)
            inter_p = (jnp.pad(inter, ((0, 0), (0, f_pad)))
                       if inter is not None else None)
            lazy_p = pad_i(lazy, 0.0) if lazy is not None else None

            contri_p = (pad_i(contri, 1.0) if contri is not None else None)

            def grow(bins, g, h, rw, fmask, key, cc, cu):
                return grow_tree(bins, g, h, rw, fmask, num_bins, default_bins,
                                 nan_bins, is_cat, mono, key, cfg,
                                 interaction_sets=inter_p, cegb_coupled=cc,
                                 cegb_lazy=lazy_p, cegb_used_data=cu,
                                 forced=forced, feature_contri=contri_p,
                                 with_stats=True)

            sharded = jax.shard_map(
                grow, mesh=mesh,
                in_specs=(P(None, axis), P(), P(), P(), P(), P(), P(), P()),
                out_specs=(P(), P(), P()), check_vma=False)

            @jax.jit
            def fn(bins, g, h, rw, fmask, key, cegb_coupled, cegb_used,
                   hist_store):            # None: each shard's store is its own
                if f_pad:
                    bins = jnp.pad(bins, ((0, 0), (0, f_pad)))
                    fmask = jnp.pad(fmask, (0, f_pad))
                    if cegb_coupled is not None:
                        cegb_coupled = jnp.pad(cegb_coupled, (0, f_pad))
                    if cegb_used is not None:
                        cegb_used = jnp.pad(cegb_used, ((0, 0), (0, f_pad)))
                return sharded(bins, g, h, rw, fmask, key,
                               cegb_coupled, cegb_used)
            return fn

        # data / voting: rows sharded
        n_pad = (-n) % ns

        def grow(bins, g, h, rw, fmask, key, cc, cu):
            return grow_tree(bins, g, h, rw, fmask, dd.num_bins,
                             dd.default_bins, dd.nan_bins, dd.is_categorical,
                             dd.monotone, key, cfg, interaction_sets=inter,
                             cegb_coupled=cc, cegb_lazy=lazy,
                             cegb_used_data=cu, forced=forced, efb=dd.efb,
                             feature_contri=contri, with_stats=True)

        sharded = jax.shard_map(
            grow, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(), P(), P(),
                      P(axis)),
            out_specs=(P(), P(axis), P()), check_vma=False)

        @jax.jit
        def fn(bins, g, h, rw, fmask, key, cegb_coupled, cegb_used,
               hist_store):                # None: each shard's store is its own
            if n_pad:
                # pad rows to a mesh multiple; zero weight excludes them from
                # every histogram/sum, so results match serial exactly
                bins = jnp.pad(bins, ((0, n_pad), (0, 0)))
                g = jnp.pad(g, (0, n_pad))
                h = jnp.pad(h, (0, n_pad))
                rw = jnp.pad(rw, (0, n_pad))
                if cegb_used is not None:
                    cegb_used = jnp.pad(cegb_used, ((0, n_pad), (0, 0)))
            tree, na, stats = sharded(bins, g, h, rw, fmask, key,
                                      cegb_coupled, cegb_used)
            return tree, (na[:n] if n_pad else na), stats
        return fn

    def _cegb_state(self):
        """Per-model CEGB accumulators, created lazily on first use."""
        coupled, lazy = self._cegb_vectors()
        if coupled is not None and not hasattr(self, "_cegb_feat_used"):
            self._cegb_feat_used = np.zeros(self.train_data.num_features, bool)
        if lazy is not None and not hasattr(self, "_cegb_used_data"):
            self._cegb_used_data = jnp.zeros(
                (self.train_data.num_data, self.train_data.num_features), bool)
        coupled_arg = None
        if coupled is not None:
            coupled_arg = jnp.where(jnp.asarray(self._cegb_feat_used), 0.0, coupled)
        used_arg = self._cegb_used_data if lazy is not None else None
        return coupled_arg, used_arg

    def _cegb_update(self, tree_arrays, node_assign, bag_mask):
        """Fold one finished tree into the model-level CEGB state.

        Rows were in a node at split time iff that node is an ancestor of the
        row's final leaf, so the per-row feature costs paid by this tree are
        exactly the features on each row's root->leaf path."""
        nl = int(tree_arrays.num_leaves)
        if nl <= 1:
            return
        if hasattr(self, "_cegb_feat_used"):
            feats = np.asarray(tree_arrays.split_feature[:nl - 1], np.int64)
            self._cegb_feat_used[feats[feats >= 0]] = True
        if hasattr(self, "_cegb_used_data"):
            L = self._grower_cfg.num_leaves
            path = np.zeros((L, self.train_data.num_features), bool)
            left = np.asarray(tree_arrays.left_child)
            right = np.asarray(tree_arrays.right_child)
            feat = np.asarray(tree_arrays.split_feature)
            stack = [(0, [])]
            while stack:
                node, fs = stack.pop()
                if node < 0:           # ~leaf_id
                    path[~node, fs] = True
                    continue
                if feat[node] < 0:
                    continue
                fs2 = fs + [feat[node]]
                stack.append((int(left[node]), fs2))
                stack.append((int(right[node]), fs2))
            paid = jnp.asarray(path)[node_assign]
            if bag_mask is not None:
                paid = paid & (bag_mask > 0)[:, None]
            self._cegb_used_data = self._cegb_used_data | paid

    @functools.cached_property
    def _predict_leaf_jit(self):
        dd = self._dd

        # rows the grower did not pass, down the new tree: a validation set
        # on the serial path, the whole training set where a bag was grown
        @jax.jit
        @jax.named_scope("lgbm/valid_traverse")
        def predict_leaf(tree_arrays, bins):
            return predict_leaf_binned(tree_arrays, bins, dd.nan_bins,
                                       efb=dd.efb)
        return predict_leaf

    # ------------------------------------------------------------------
    def eval_current(self) -> List[Tuple[str, str, float, bool]]:
        """Evaluate all metrics on train (if enabled) + valid sets.
        Returns (dataset_name, metric_name, value, higher_better)."""
        out = []
        if self.config.is_provide_training_metric and self.train_metrics:
            out += self.eval_scores(self.train_data_name, self._train_score,
                                    self.train_metrics)
        for vi in range(len(self.valid_sets)):
            out += self.eval_scores(self.valid_names[vi],
                                    self._valid_scores[vi],
                                    self.valid_metrics[vi])
        return out

    def eval_scores(self, data_name: str, score_dev, metrics) -> List[tuple]:
        """One data set's metrics over its scores, as ``lgbm/eval`` with three
        kinds of children: ``wait`` (the device finishing the scores' last
        update, a whole tree where the host ran ahead), ``fetch`` (the
        device-to-host copy and the float64 cast, where a metric runs on the
        host) and one ``metric`` each, whose ``on_device`` says where it ran:
        a metric with a device form computes where device scores of one tree
        an iteration live, and only its result is fetched."""
        out = []
        tracer = get_tracer()
        scores_on_device = (isinstance(score_dev, jax.Array)
                            and self.num_tree_per_iteration == 1)
        on_device = [scores_on_device and m.has_device_form(self.objective)
                     for m in metrics]
        with tracer.span("lgbm/eval", iteration=self.iter_ - 1,
                         data=data_name):
            with tracer.span("lgbm/eval/wait"):
                jax.block_until_ready(score_dev)
            if not all(on_device):
                with tracer.span("lgbm/eval/fetch"):
                    score = np.asarray(score_dev, np.float64)
                s = score[0] if self.num_tree_per_iteration == 1 else score
            for m, dev in zip(metrics, on_device):
                with tracer.span("lgbm/eval/metric", metric=type(m).__name__,
                                 on_device=int(dev)):
                    rows = (m.device_eval(score_dev, self.objective) if dev
                            else m.eval(s, self.objective))
                obs_metrics.counter("eval.metrics_on_device" if dev
                                    else "eval.metrics_on_host").inc()
                out.extend((data_name, name, val, hib)
                           for name, val, hib in rows)
        return out

    # ------------------------------------------------------------------
    # row*tree volume above which the stacked device traversal beats the
    # host loop (compile cost amortizes); overridable via config.pred_device
    _DEVICE_PREDICT_MIN_WORK = 2_000_000

    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    start_iteration: int = 0) -> np.ndarray:
        """Raw scores [N] or [N, K] (reference ``GBDT::PredictRaw``).

        Large requests run as ONE compiled device program over the stacked
        ensemble (``ops/ensemble.py``) instead of a per-tree host loop —
        the TPU analog of the reference's OpenMP block predictor
        (``gbdt_prediction.cpp:20-72``)."""
        if _is_sparse_mat(X):
            return _blockwise_sparse(
                X, lambda d: self.predict_raw(d, num_iteration, start_iteration))
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        K = self.num_tree_per_iteration
        n_iters = len(self.models) // K
        if num_iteration is not None and num_iteration > 0:
            n_iters = min(n_iters, num_iteration)
        models = self.models[start_iteration * K:(start_iteration + n_iters) * K]

        mode = getattr(self.config, "pred_device", "auto")
        early_stop = (self.config.pred_early_stop
                      and self.objective is not None
                      and getattr(self.objective, "name", "") in
                      ("binary", "multiclass", "multiclassova"))
        use_device = models and not early_stop and mode != "host" and (
            mode == "device"
            or X.shape[0] * len(models) >= self._DEVICE_PREDICT_MIN_WORK)
        if use_device:
            out = self._predict_raw_device(models, start_iteration, X)
        elif early_stop:
            out = self._predict_raw_early_stop(models, X, K)
        else:
            out = np.zeros((X.shape[0], K))
            for ti, t in enumerate(models):
                out[:, ti % K] += t.predict(X)
        return out[:, 0] if K == 1 else out

    def _predict_raw_early_stop(self, models, X: np.ndarray, K: int):
        """Margin-based per-row prediction early termination (reference
        ``prediction_early_stop.cpp``): every ``pred_early_stop_freq`` trees,
        rows whose margin — ``2*|score|`` for binary, top1−top2 for
        multiclass — exceeds ``pred_early_stop_margin`` stop accumulating
        further trees."""
        cfg = self.config
        # round the check period up to an iteration boundary: freezing a row
        # mid-iteration would leave unequal per-class tree counts
        freq = max(1, cfg.pred_early_stop_freq) * K
        thresh = cfg.pred_early_stop_margin
        n = X.shape[0]
        out = np.zeros((n, K))
        active = np.ones(n, bool)
        for ti, t in enumerate(models):
            out[active, ti % K] += t.predict(X[active])
            if (ti + 1) % freq == 0 and ti + 1 < len(models):
                if K == 1:
                    margin = 2.0 * np.abs(out[:, 0])
                else:
                    part = np.partition(out, K - 2, axis=1)
                    margin = part[:, K - 1] - part[:, K - 2]
                active &= margin <= thresh
                if not active.any():
                    break
        return out

    def _predict_raw_device(self, models, start_iteration: int,
                            X: np.ndarray) -> np.ndarray:
        from ..ops.ensemble import predict_raw_ensemble, stack_trees
        key = (start_iteration, len(models), len(self.models))
        cache = getattr(self, "_ens_cache", None)
        if cache is None or cache[0] != key:
            self._ens_cache = (key, stack_trees(models))
        ens = self._ens_cache[1]
        K = self.num_tree_per_iteration
        any_linear = any(getattr(t, "is_linear", False) for t in models)
        fn = jax.jit(predict_raw_ensemble, static_argnums=(2, 3))
        out = np.zeros((X.shape[0], K))
        step = 1 << 22                      # bound device residency of X
        for s in range(0, X.shape[0], step):
            chunk = jnp.asarray(X[s:s + step], jnp.float32)
            out[s:s + step] = np.asarray(fn(ens, chunk, K, any_linear),
                                         np.float64).T
        return out

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                start_iteration: int = 0, raw_score: bool = False) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration, start_iteration)
        if raw_score or self.objective is None:
            return raw
        if self.num_tree_per_iteration > 1:
            return np.asarray(self.objective.convert_output(raw.T)).T
        return np.asarray(self.objective.convert_output(raw))

    def predict_contrib(self, X: np.ndarray, num_iteration: int = -1,
                        start_iteration: int = 0, sparse: bool = False,
                        sparse_format: "str | None" = None):
        """TreeSHAP feature contributions (reference ``GBDT::PredictContrib``
        via ``Tree::TreeSHAP``, ``tree.cpp:887``): per row, per class,
        ``[num_features + 1]`` with the bias (expected value) last.

        ``sparse=True`` returns scipy CSR (one matrix, or a list of K for
        multiclass) built block by block, so a wide-sparse input never
        materializes the full dense contribution matrix — the analog of the
        reference's ``LGBM_BoosterPredictSparseOutput``
        (``src/c_api.cpp:1900``) and the python package's sparse-in →
        sparse-out contract."""
        from ..ops.shap import tree_shap, expected_value
        if any(getattr(t, "is_linear", False) for t in self.models):
            raise LightGBMError(
                "pred_contrib (TreeSHAP) is not supported for linear trees")
        if sparse:
            return self._predict_contrib_sparse(X, num_iteration,
                                                start_iteration,
                                                sparse_format)
        if _is_sparse_mat(X):
            return _blockwise_sparse(
                X, lambda d: self.predict_contrib(d, num_iteration,
                                                  start_iteration))
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        n, F = X.shape
        K = self.num_tree_per_iteration
        n_iters = len(self.models) // K
        if num_iteration is not None and num_iteration > 0:
            n_iters = min(n_iters, num_iteration)
        out = np.zeros((n, K, F + 1))
        for i in range(start_iteration, start_iteration + n_iters):
            for k in range(K):
                ti = i * K + k
                if ti < len(self.models):
                    t = self.models[ti]
                    out[:, k, :F] += tree_shap(t, X)
                    out[:, k, F] += expected_value(t)
        return out[:, 0, :] if K == 1 else out.reshape(n, K * (F + 1))

    def _predict_contrib_sparse(self, X, num_iteration: int,
                                start_iteration: int,
                                sparse_format: "str | None" = None):
        """Blockwise sparse TreeSHAP: CSR per block, stacked — peak memory
        is one dense block, not the [n, F+1] matrix.  The block row count
        is capped by total ELEMENTS, so a wide-sparse input (the case this
        path exists for) still bounds the dense scratch."""
        import scipy.sparse as sp
        K = self.num_tree_per_iteration
        Xc = X.tocsr() if _is_sparse_mat(X) else np.asarray(X, np.float64)
        n, F = Xc.shape
        block = max(1, min(_SPARSE_PREDICT_BLOCK,
                           (64 << 20) // max(1, (F + 1) * K)))
        blocks: List[list] = [[] for _ in range(K)]
        for s in range(0, max(n, 1), block):
            xb = Xc[s:s + block]
            if _is_sparse_mat(xb):
                xb = np.asarray(xb.toarray(), np.float64)
            dense = self.predict_contrib(xb, num_iteration, start_iteration)
            if K == 1:
                blocks[0].append(sp.csr_matrix(dense))
            else:
                F1 = dense.shape[1] // K
                for k in range(K):
                    blocks[k].append(
                        sp.csr_matrix(dense[:, k * F1:(k + 1) * F1]))
        # format-preserving like the reference python package: CSC in ->
        # CSC out (LGBM_BoosterPredictSparseOutput handles both layouts);
        # the caller passes the ORIGINAL input format (Booster.predict
        # normalizes the matrix to CSR before the blocks are cut)
        fmt = sparse_format or (getattr(X, "format", "csr")
                                if _is_sparse_mat(X) else "csr")
        fmt = fmt if fmt in ("csr", "csc") else "csr"
        mats = [sp.vstack(b, format=fmt) if len(b) > 1
                else (b[0] if fmt == "csr" else b[0].tocsc())
                for b in blocks]
        return mats[0] if K == 1 else mats

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        if _is_sparse_mat(X):
            return _blockwise_sparse(
                X, lambda d: self.predict_leaf_index(d, num_iteration))
        X = np.asarray(X, dtype=np.float64)
        if X.ndim == 1:
            X = X.reshape(1, -1)
        K = self.num_tree_per_iteration
        n_iters = len(self.models) // K
        if num_iteration is not None and num_iteration > 0:
            n_iters = min(n_iters, num_iteration)
        out = np.zeros((X.shape[0], n_iters * K), np.int32)
        for i in range(n_iters * K):
            out[:, i] = self.models[i].predict_leaf_index(X)
        return out

    # ------------------------------------------------------------------
    def continue_from(self, prev: "GBDT") -> None:
        """Continued training from an existing model (reference CLI
        ``input_model`` / Python ``init_model``: ``boosting.cpp:35-60``,
        ``engine.py:15``): adopt the previous ensemble and warm up the
        cached train/valid scores with its predictions over the binned data."""
        import copy
        check(prev.num_tree_per_iteration == self.num_tree_per_iteration,
              "init_model has a different number of tree per iteration")
        self.models = [copy.deepcopy(t) for t in prev.models]
        self._tree_weights = list(prev._tree_weights) or [1.0] * len(self.models)
        self._device_trees = []
        self._ens_cache = None
        K = self.num_tree_per_iteration
        self.iter_ = len(self.models) // K

        has_linear = any(getattr(t, "is_linear", False) for t in self.models)

        def warm(ds, dd, score, raw):
            # host-side binned traversal wants per-feature bins: decode any
            # EFB bundle columns (io/efb.py)
            bins_np = ds.unbundled_bins()
            nan_np = np.asarray(dd.nan_bins)
            s = np.array(score, np.float64)
            for t in self.models:
                if len(t.cat_boundaries) > 1:
                    # text-loaded trees carry VALUE bitsets only; binned
                    # traversal needs the bin-space ones
                    t.bin_cat_bitsets(self.train_data.bin_mappers)
                # ... and VALUE thresholds only: without this, a file-based
                # init_model warmed the scores with all-zero bin thresholds
                t.bin_numeric_thresholds(self.train_data.bin_mappers)
            for i, t in enumerate(self.models):
                if getattr(t, "is_linear", False):
                    # linear leaves need raw values (binned midpoints would
                    # warm the scores away from the model's true predictions)
                    s[i % K] = s[i % K] + t.predict(raw)
                else:
                    s[i % K] = s[i % K] + t.predict_binned(bins_np, nan_np)
            return jnp.asarray(s.astype(np.float32))

        def raw_of(ds):
            if not has_linear:
                return None
            if ds.raw_data is None:
                raise LightGBMError(
                    "continued training from a linear-tree model requires "
                    "the Dataset to keep raw values (pass linear_tree=true)")
            return np.asarray(ds.raw_data, np.float64)

        # the first tree of the previous model already carries its bias;
        # drop this model's own boost-from-average init
        self._train_score = warm(self.train_data, self._dd,
                                 jnp.zeros_like(self._train_score),
                                 raw_of(self.train_data))
        for vi, vset in enumerate(self.valid_sets):
            # device_meta, not device_data: warm() only reads nan_bins, and
            # under the streaming engine a full device_data() here would
            # materialize (and cache) a valid bin matrix the budget says
            # does not fit
            self._valid_scores[vi] = warm(vset, vset.device_meta(),
                                          jnp.zeros_like(self._valid_scores[vi]),
                                          raw_of(vset))

    # ------------------------------------------------------------------
    def refit(self, X: np.ndarray, y: np.ndarray, decay_rate: float = 0.9) -> None:
        """Refit the existing tree structures on new data (reference
        ``GBDT::RefitTree`` (``gbdt.cpp:285``) + ``FitByExistingTree``
        (``serial_tree_learner.cpp:211-250``)): per iteration, gradients at
        the progressive score are re-aggregated per leaf and
        ``new = output*shrinkage``, ``leaf = decay*old + (1-decay)*new``."""
        from ..objective import create_objective
        from ..io.dataset import Metadata
        if any(getattr(t, "is_linear", False) for t in self.models):
            raise LightGBMError(
                "refit is not supported for linear-tree models yet")
        cfg = self.config
        X = np.asarray(X, np.float64)
        n = X.shape[0]
        obj = self.objective
        if obj is None:
            obj = create_objective(cfg)
        if obj is None:
            raise LightGBMError("cannot refit without an objective")
        md = Metadata(n)
        md.set_field("label", y)
        obj.init(md, n)
        K = self.num_tree_per_iteration
        n_iters = len(self.models) // K
        label_dev = jnp.asarray(md.label)
        score = np.zeros((K, n), np.float32)
        leaf_idx = [t.predict_leaf_index(X) for t in self.models]
        lam1, lam2, mds = cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step

        def out_of(sg, sh):
            thr = np.sign(sg) * np.maximum(np.abs(sg) - lam1, 0.0)
            o = -thr / (sh + lam2 + 1e-35)
            if mds > 0:
                o = np.clip(o, -mds, mds)
            return o

        for it in range(n_iters):
            sc = jnp.asarray(score)
            if K > 1:
                g, h = obj.get_gradients_multi(sc, label_dev, None)
            else:
                g0, h0 = obj.get_gradients(sc[0], label_dev, None)
                g, h = g0[None, :], h0[None, :]
            g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
            for k in range(K):
                t = self.models[it * K + k]
                lp = leaf_idx[it * K + k]
                nl = t.num_leaves
                sg = np.bincount(lp, weights=g[k], minlength=nl)[:nl]
                sh = np.bincount(lp, weights=h[k], minlength=nl)[:nl] + 1e-15
                new_out = out_of(sg, sh) * t.shrinkage
                t.leaf_value = (decay_rate * t.leaf_value
                                + (1.0 - decay_rate) * new_out)
                score[k] += t.leaf_value[lp].astype(np.float32)
        self._device_trees = []            # host trees changed; drop caches
        self._ens_cache = None

    # ------------------------------------------------------------------
    def rollback_one_iter(self) -> None:
        """Reference ``GBDT::RollbackOneIter`` (``gbdt.cpp:454``): undo the
        last iteration's trees and restore cached scores (one-step history)."""
        if self.iter_ <= 0:
            return
        if self._prev_scores is None:
            raise LightGBMError("rollback history exhausted (only one step kept)")
        K = self.num_tree_per_iteration
        self.models = self.models[:-K]
        self._device_trees = self._device_trees[:-K]
        self._tree_weights = self._tree_weights[:-K]
        self._ens_cache = None
        self.iter_ -= 1
        # the rolled-back iteration's empty-tree accounting must not leak
        # into a retrain of the same iteration (or pin _stop_flag)
        self._empty_by_iter.pop(self.iter_, None)
        self._stop_flag = False
        self._train_score, self._valid_scores = self._prev_scores
        self._prev_scores = None

    @property
    def num_trees(self) -> int:
        return len(self.models)

    def feature_importance(self, importance_type: str = "split",
                           iteration: int = -1) -> np.ndarray:
        """split/gain importance (reference ``GBDT::FeatureImportance``,
        ``gbdt.cpp:606``)."""
        n_feat = self.max_feature_idx + 1
        imp = np.zeros(n_feat)
        models = self.models
        if iteration is not None and iteration > 0:
            models = models[:iteration * self.num_tree_per_iteration]
        for tree in models:
            for j in range(tree.num_internal):
                if tree.num_leaves > 1 and tree.split_gain[j] > 0:
                    f = tree.split_feature[j]
                    if importance_type == "split":
                        imp[f] += 1
                    else:
                        imp[f] += tree.split_gain[j]
        return imp


def bag_mask_from_uniform(cfg: Config, u, label):
    """Bernoulli bagging mask from a per-row uniform draw (the shared math
    of GBDT._bagging_weights and the distributed trainer — the two paths
    must stay byte-identical for multi-process parity, so the formula
    lives ONCE here; reference gbdt.cpp:182-262)."""
    if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
        frac = jnp.where(label > 0, cfg.pos_bagging_fraction,
                         cfg.neg_bagging_fraction)
    else:
        frac = cfg.bagging_fraction
    return (u < frac).astype(jnp.float32)

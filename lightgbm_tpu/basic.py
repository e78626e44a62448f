"""Python-facing core objects: ``Dataset`` and ``Booster``.

API-parity layer mirroring the reference's ``python-package/lightgbm/basic.py``
(``Dataset`` :935, ``Booster`` :2043) — but there is no ctypes/C-ABI boundary:
the engine is the in-process JAX ``GBDT``.  Lazy Dataset construction,
reference alignment for validation data, field get/set, model IO, and the
predict family keep the same surface.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .config import Config
from .io.dataset import Dataset as _InnerDataset
from .models.gbdt import GBDT
from .models import model_io
from .obs import get_tracer
from .utils.log import Log, check, LightGBMError

__all__ = ["Dataset", "Booster", "LightGBMError"]


class Dataset:
    """Lazily-constructed dataset (reference ``basic.py:935``)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 silent: bool = False,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List[int], List[str]] = "auto",
                 params: Optional[Dict[str, Any]] = None, free_raw_data: bool = True):
        # ``silent`` sits at the reference's position (basic.py:938) and,
        # like the reference, injects verbose=-1 unless the user set a
        # verbosity themselves
        self.silent = silent
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._inner: Optional[_InnerDataset] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        # per-categorical-column category lists for pandas inputs (reference
        # pandas_categorical, basic.py:391); filled at construct time
        self.pandas_categorical = None

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._inner is not None:
            return self
        if self.silent and not any(a in self.params for a in (
                "verbose", "verbosity")):
            self.params["verbose"] = -1
        cfg = Config.from_params(self.params)
        data = self.data
        if isinstance(data, str):
            from .io.loader import load_file
            import os as _os
            path = data
            data, label, feat_names, fweight, fgroup = load_file(path, cfg)
            if self.label is None:
                self.label = label
            # weight_column / group_column roles (reference Metadata::Init)
            if self.weight is None and fweight is not None:
                self.weight = fweight
            if self.group is None and fgroup is not None:
                self.group = fgroup
            if self.feature_name == "auto" and feat_names:
                self.feature_name = feat_names
            # sidecar metadata files, auto-detected like the reference
            # (Metadata::Init file loaders, src/io/metadata.cpp:
            # <data>.weight one weight per row, <data>.query group sizes,
            # <data>.init init scores)
            if self.weight is None and _os.path.exists(path + ".weight"):
                self.weight = np.loadtxt(path + ".weight", dtype=np.float64,
                                         ndmin=1)
            if self.group is None and _os.path.exists(path + ".query"):
                self.group = np.loadtxt(path + ".query",
                                        dtype=np.int64).reshape(-1)
            if self.init_score is None and _os.path.exists(path + ".init"):
                self.init_score = np.loadtxt(path + ".init", dtype=np.float64,
                                             ndmin=1)
        from .io.dataset import _is_dataframe
        if _is_dataframe(data):
            from .io.dataset import _pandas_to_numpy
            if self.reference is not None:
                # the reference owns the category lists; make sure it is
                # constructed BEFORE they are read (an early-constructed
                # valid set must not code against its own levels)
                self.reference.construct()
            ref_pc = (self.reference.pandas_categorical
                      if self.reference is not None else None)
            if self.reference is not None:
                from .io.dataset import _require_pandas_mapping
                _require_pandas_mapping(data, ref_pc, "validation DataFrame")
            data, df_names, cat_spec, self.pandas_categorical = \
                _pandas_to_numpy(data, self.categorical_feature, ref_pc)
            if self.feature_name == "auto":
                self.feature_name = df_names
            self.categorical_feature = cat_spec
        feature_names = None if self.feature_name == "auto" else list(self.feature_name)
        cats = None
        if self.categorical_feature != "auto":
            cats = self.categorical_feature
        ref_inner = None
        if self.reference is not None:
            ref_inner = self.reference.construct()._inner
        if self.used_indices is not None and ref_inner is not None:
            self._inner = ref_inner.subset(self.used_indices)
            if self.label is not None:
                self._inner.metadata.set_field("label", np.asarray(self.label)[self.used_indices] if len(np.asarray(self.label)) != len(self.used_indices) else self.label)
        else:
            # resolve categorical feature names -> indices
            if cats is not None and feature_names is not None:
                cats = [feature_names.index(c) if isinstance(c, str) else c for c in cats]
            from .io.dataset import _is_sparse, is_shards
            self._inner = _InnerDataset.from_data(
                data if (hasattr(data, "values") or _is_sparse(data)
                         or is_shards(data))
                else np.asarray(data, dtype=np.float64),
                cfg, label=self.label, weight=self.weight, group=self.group,
                init_score=self.init_score, categorical_feature=cats,
                feature_names=feature_names, reference=ref_inner)
        if self.free_raw_data and not isinstance(self.data, str):
            pass  # keep raw for sklearn compat; TPU copy is the binned matrix
        return self

    # ------------------------------------------------------------------
    def set_field(self, name: str, data) -> None:
        self.construct()
        self._inner.metadata.set_field(name, data)

    def get_field(self, name: str):
        self.construct()
        return self._inner.metadata.get_field(name)

    def set_label(self, label) -> None:
        self.label = label
        if self._inner is not None:
            self._inner.metadata.set_field("label", label)

    def set_weight(self, weight) -> None:
        self.weight = weight
        if self._inner is not None:
            self._inner.metadata.set_field("weight", weight)

    def set_group(self, group) -> None:
        self.group = group
        if self._inner is not None:
            self._inner.metadata.set_field("group", group)

    def set_init_score(self, init_score) -> None:
        self.init_score = init_score
        if self._inner is not None:
            self._inner.metadata.set_field("init_score", init_score)

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_group(self):
        qb = self.get_field("group")
        return None if qb is None else np.diff(qb)

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        self.construct()
        return self._inner.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._inner.num_total_features

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._inner.feature_names)

    # ------------------------------------------------------------------
    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, silent: bool = False,
                     params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score, silent=silent,
                       params=params or self.params)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        ds = Dataset(None, reference=self, params=params or self.params)
        ds.used_indices = np.asarray(used_indices, dtype=np.int64)
        return ds

    def save_binary(self, filename: str) -> "Dataset":
        self.construct()
        self._inner.save_binary(filename)
        return self

    # -- misc public surface mirroring the reference Dataset ------------
    def get_data(self):
        """The raw data this Dataset was built from (reference
        ``Dataset.get_data``; None when constructed from a binary cache)."""
        return self.data

    def get_params(self) -> Dict[str, Any]:
        return dict(self.params)

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        if categorical_feature == self.categorical_feature:
            return self
        if self._inner is not None:
            if self.data is None:
                raise LightGBMError(
                    "Cannot set categorical feature after freed raw data; "
                    "set free_raw_data=False when constructing the Dataset")
            self._inner = None          # raw data held: re-bin lazily
        self.categorical_feature = categorical_feature
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        self.feature_name = feature_name
        if self._inner is not None:
            from .io.dataset import _sanitize_feature_names
            names = _sanitize_feature_names(list(feature_name))
            check(len(names) == self._inner.num_total_features,
                  "Length of feature names doesn't equal with num_feature")
            self._inner.feature_names = names
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        if self._inner is not None:
            raise LightGBMError(
                "Cannot set reference after the Dataset was constructed")
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """Set of Datasets reachable via reference links (reference
        ``Dataset.get_ref_chain``)."""
        head = self
        ref_chain = set()
        while len(ref_chain) < ref_limit:
            if isinstance(head, Dataset):
                ref_chain.add(head)
                if head.reference is not None and head.reference not in ref_chain:
                    head = head.reference
                else:
                    break
            else:
                break
        return ref_chain

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Stack another Dataset's features onto this one column-wise
        (reference ``Dataset.add_features_from`` / ``Dataset::AddFeaturesFrom``).
        Both must still hold raw data (pre- or post-construct) and agree on
        row count; the merged Dataset re-bins lazily."""
        if (self.data is None or other.data is None
                or isinstance(self.data, str) or isinstance(other.data, str)):
            raise LightGBMError(
                "Cannot add features from a Dataset without in-memory raw "
                "data (file-backed or freed Datasets are not mergeable)")
        a, b = self.data, other.data
        if hasattr(a, "values"):
            a = a.values
        if hasattr(b, "values"):
            b = b.values
        check(a.shape[0] == b.shape[0], "Datasets must have equal rows")
        width_a = a.shape[1]
        if hasattr(a, "tocsr") or hasattr(b, "tocsr"):
            import scipy.sparse as sps
            merged = sps.hstack([sps.csr_matrix(a), sps.csr_matrix(b)],
                                format="csr")
        else:
            merged = np.concatenate([np.asarray(a, np.float64),
                                     np.asarray(b, np.float64)], axis=1)
        self.data = merged
        if (isinstance(self.feature_name, list)
                and isinstance(other.feature_name, list)):
            self.feature_name = list(self.feature_name) + list(other.feature_name)
        # merge categorical designations: integer indices of ``other`` shift
        # by this Dataset's pre-merge width; name-based entries ride the
        # feature_name merge untouched
        oc = other.categorical_feature
        if oc != "auto" and oc:
            shifted = [c + width_a if isinstance(c, (int, np.integer)) else c
                       for c in oc]
            mine = ([] if self.categorical_feature == "auto"
                    else list(self.categorical_feature))
            self.categorical_feature = mine + shifted
        self._inner = None                  # force re-construction
        return self

    def num_bins_total(self) -> int:
        self.construct()
        return int(sum(self._inner.num_bin(i) for i in range(self._inner.num_features)))


class Booster:
    """Training/prediction handle (reference ``basic.py:2043``)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 silent: bool = False):
        self.params = dict(params or {})
        self.silent = silent
        if silent and not any(a in self.params for a in
                              ("verbose", "verbosity")):
            self.params["verbose"] = -1     # reference Booster(silent=True)
        self.train_set = train_set
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self.pandas_categorical = None
        if train_set is not None:
            check(isinstance(train_set, Dataset), "training data should be Dataset instance")
            cfg = Config.from_params(self.params)
            train_set.params = dict(self.params)
            train_set.construct()
            self.pandas_categorical = train_set.pandas_categorical
            with get_tracer().span("lgbm/booster/init"):
                self._gbdt = self._create_engine(cfg, train_set._inner)
            self.name_valid_sets: List[str] = []
        elif model_file is not None:
            with open(model_file) as f:
                self._load_from_string(f.read())
        elif model_str is not None:
            self._load_from_string(model_str)
        else:
            raise LightGBMError("need at least one of train_set / model_file / model_str")

    @staticmethod
    def _create_engine(cfg: Config, inner_train):
        # out-of-core routing (lightgbm_tpu/stream, docs/STREAMING.md): when
        # the projected device footprint exceeds the configured budget (or
        # stream_rows forces it), train from host RAM in streamed row blocks
        plan = (inner_train.stream_plan() if inner_train is not None
                else None)
        if plan is not None:
            from .stream.booster import StreamGBDT, StreamGOSS
            scls = {"gbdt": StreamGBDT, "goss": StreamGOSS}.get(cfg.boosting)
            if scls is None:
                raise LightGBMError(
                    "out-of-core streaming supports boosting=gbdt/goss "
                    f"(got {cfg.boosting}); raise max_bin_matrix_bytes or "
                    "unset stream_rows to train device-resident")
            return scls(cfg, inner_train)
        from .models.dart import DART
        from .models.goss import GOSS
        from .models.rf import RF
        cls = {"gbdt": GBDT, "dart": DART, "goss": GOSS, "rf": RF}[cfg.boosting]
        return cls(cfg, inner_train)

    def _load_from_string(self, model_str: str) -> None:
        self._gbdt = model_io.load_model_from_string(model_str, GBDT)
        self.name_valid_sets = []
        self.pandas_categorical = model_io.parse_pandas_categorical(model_str)

    # ------------------------------------------------------------------
    def add_valid(self, data: Dataset, name: str) -> "Booster":
        data.params = dict(self.params)
        data.construct()
        self._gbdt.add_valid_data(data._inner, name)
        self.name_valid_sets.append(name)
        if not hasattr(self, "valid_sets_py"):
            self.valid_sets_py: List[Dataset] = []
        self.valid_sets_py.append(data)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if stopped (no splits)
        (reference ``Booster.update``, ``basic.py:2448``)."""
        if train_set is not None:
            raise LightGBMError("resetting train_set after construction is not supported yet")
        if fobj is not None:
            K = self._gbdt.num_tree_per_iteration
            score = self.__inner_raw_score()
            grad, hess = fobj(score, self.train_set)
            return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))
        return self._gbdt.train_one_iter()

    def __inner_raw_score(self):
        s = np.asarray(self._gbdt._train_score, np.float64)
        return s[0] if self._gbdt.num_tree_per_iteration == 1 else s.T.reshape(-1)

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def refit(self, data, label, decay_rate: float = 0.9) -> "Booster":
        """Refit existing tree structures on new data (reference
        ``Booster.refit``, ``basic.py``; ``GBDT::RefitTree``)."""
        self._gbdt.refit(np.asarray(data, np.float64), label, decay_rate)
        return self

    # -- misc public surface mirroring the reference Booster ------------
    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Re-apply training parameters mid-run (reference
        ``Booster.reset_parameter`` -> ``GBDT::ResetConfig``).  Compile-time
        grower parameters (num_leaves, min_data_in_leaf, ...) force a
        re-jit of the grow program on the next iteration."""
        # dataset-level parameters are baked into the binned matrix — a
        # change here could not take effect (or worse: a smaller max_bin
        # would shrink the histogram under already-binned indices).  The
        # reference's ResetConfig rejects these the same way.
        _DATASET_PARAMS = {
            "max_bin", "max_bin_by_feature", "min_data_in_bin",
            "bin_construct_sample_cnt", "data_random_seed", "use_missing",
            "zero_as_missing", "feature_pre_filter", "enable_bundle",
            "categorical_feature", "linear_tree", "pre_partition",
        }
        cfgcls = Config
        bad = sorted(_DATASET_PARAMS
                     & {cfgcls.resolve_alias(str(k)) for k in params})
        if bad and self._gbdt.train_data is not None:
            raise LightGBMError(
                "Cannot change dataset parameters %s after the Dataset was "
                "constructed; rebuild the Dataset instead" % bad)
        self.params.update(params)
        gbdt = self._gbdt
        gbdt.config.update(params)
        gbdt.config.finalize()
        if "learning_rate" in params:
            gbdt.shrinkage_rate = float(gbdt.config.learning_rate)
        if gbdt.train_data is not None:
            old = gbdt._grower_cfg
            # re-graft the mesh fields _setup_parallel added — rebuilding
            # from scratch would silently turn a parallel learner serial
            # while _mesh stays set
            new = gbdt._make_grower_cfg()._replace(
                axis_name=old.axis_name, parallel_mode=old.parallel_mode,
                num_shards=old.num_shards, top_k=old.top_k)
            if new != old:
                # only a genuine compile-time change pays the re-jit; pure
                # runtime params (learning_rate schedules fire every
                # iteration) must not retrace the grower
                gbdt._grower_cfg = new
                gbdt.__dict__.pop("_grow_jit", None)
                gbdt._recorded_programs.discard("train.grow_tree")
        return self

    def attr(self, key: str):
        """Get a free-form attribute (reference ``Booster.attr``)."""
        return getattr(self, "_attr", {}).get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """Set (or with value None, delete) free-form attributes."""
        store = getattr(self, "_attr", None)
        if store is None:
            store = self._attr = {}
        for k, v in kwargs.items():
            if v is None:
                store.pop(k, None)
            else:
                store[k] = str(v)
        return self

    def lower_bound(self) -> float:
        """Lower bound of raw prediction: sum of per-tree minimum leaf
        values (reference ``LGBM_BoosterGetLowerBoundValue``)."""
        return float(sum(float(np.min(t.leaf_value)) if len(t.leaf_value)
                         else 0.0 for t in self._gbdt.models))

    def upper_bound(self) -> float:
        """Upper bound of raw prediction (reference
        ``LGBM_BoosterGetUpperBoundValue``)."""
        return float(sum(float(np.max(t.leaf_value)) if len(t.leaf_value)
                         else 0.0 for t in self._gbdt.models))

    def model_from_string(self, model_str: str) -> "Booster":
        """Replace this booster's model in place (reference
        ``Booster.model_from_string``)."""
        self._load_from_string(model_str)
        return self

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Shuffle tree order in [start, end) iterations (reference
        ``Booster.shuffle_models`` -> ``GBDT::ShuffleModels``; DART
        ensembles are order-insensitive in prediction, this reshuffles
        which trees dropout sees first on continued training)."""
        gbdt = self._gbdt
        K = gbdt.num_tree_per_iteration
        models = list(gbdt.models)
        n_iters = len(models) // K
        end = n_iters if end_iteration <= 0 else min(end_iteration, n_iters)
        start = max(0, start_iteration)
        if start >= end:
            raise LightGBMError(
                f"shuffle_models: empty range [{start}, {end})")
        rng = np.random.default_rng(gbdt.config.seed)
        order = np.arange(start, end)
        rng.shuffle(order)

        def shuffle_list(lst):
            blocks = [lst[i * K:(i + 1) * K] for i in range(n_iters)]
            out = blocks[:start] + [blocks[i] for i in order] + blocks[end:]
            return [t for blk in out for t in blk]

        # device-side caches (TreeArrays, per-tree scales) ride the same
        # permutation so DART's drop/normalize indexing stays aligned
        same_len = len(gbdt._device_trees) == len(models)
        gbdt.models = shuffle_list(models)
        if same_len:
            gbdt._device_trees = shuffle_list(gbdt._device_trees)
            gbdt._tree_weights = shuffle_list(gbdt._tree_weights)
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """Name used for the training set in eval output (reference
        ``Booster.set_train_data_name``)."""
        self._train_data_name = name
        self._gbdt.train_data_name = name
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """One leaf's output value (reference ``Booster.get_leaf_output``)."""
        return float(self._gbdt.models[tree_id].leaf_value[leaf_id])

    # -- pickling: serialize through the model string, like the reference
    # Booster.__getstate__ (basic.py) -----------------------------------
    def __getstate__(self):
        return {"params": self.params,
                "best_iteration": self.best_iteration,
                "best_score": self.best_score,
                # ALL trees (num_iteration=-1): the default would truncate
                # early-stopped boosters at best_iteration on pickling
                "model_str": self.model_to_string(num_iteration=-1)}

    def __setstate__(self, state):
        self.params = state["params"]
        self.best_iteration = state["best_iteration"]
        self.best_score = state["best_score"]
        self.train_set = None
        self._load_from_string(state["model_str"])

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: Optional[int] = None) -> "Booster":
        """Set up multi-process training from a machine list (reference
        ``Booster.set_network``, ``basic.py:2206``) — delegates to
        ``parallel.mesh.set_network`` (jax.distributed bring-up);
        ``num_machines`` defaults to the machine-list length."""
        from .parallel.mesh import set_network as _set_network
        _set_network(machines, local_listen_port=local_listen_port,
                     listen_time_out=listen_time_out,
                     num_machines=num_machines)
        return self

    def free_network(self) -> "Booster":
        """Tear the process group down (reference ``Booster.free_network``)."""
        from .parallel.mesh import free_network as _free_network
        _free_network()
        return self

    def free_dataset(self) -> "Booster":
        """Drop the python-side training/validation Dataset references so
        their raw arrays can be reclaimed (reference
        ``Booster.free_dataset``).  The engine keeps its binned copy, so
        further ``update()``/eval/predict continue to work — but callbacks
        that receive the python ``Dataset`` (custom ``fobj``/``feval``)
        will see ``None`` afterwards."""
        self.train_set = None
        self.valid_sets_py = []
        return self

    def current_iteration(self) -> int:
        """Number of completed iterations (reference
        ``Booster.current_iteration()`` — a method, not a property)."""
        return self._gbdt.iter_

    def num_trees(self) -> int:
        return self._gbdt.num_trees

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        return self._gbdt.max_feature_idx + 1

    # ------------------------------------------------------------------
    def eval_train(self, feval=None):
        return self._eval_set(
            getattr(self, "_train_data_name", "training"), -1, feval)

    def eval_valid(self, feval=None):
        out = []
        for i in range(len(self.name_valid_sets)):
            out.extend(self._eval_set(self.name_valid_sets[i], i, feval))
        return out

    def eval(self, data=None, name="eval", feval=None):
        results = []
        for ds_name, metric, val, hib in self._gbdt.eval_current():
            results.append((ds_name, metric, val, hib))
        return results

    def _eval_set(self, name, idx, feval):
        if idx < 0:
            # explicit eval_train(): training metrics are computed on demand
            # regardless of is_provide_training_metric (the flag only gates
            # automatic per-iteration printing, like the reference)
            gb = self._gbdt
            out = []
            # boosters loaded from model text have no training data/metrics
            if getattr(gb, "train_metrics", None) and gb._train_score is not None:
                out = gb.eval_scores(name, gb._train_score, gb.train_metrics)
        else:
            all_results = self._gbdt.eval_current()
            out = [(n, m, v, h) for (n, m, v, h) in all_results if n == name]
        out.extend(self._feval_results(name, idx, feval))
        return out

    def _feval_results(self, name, idx, feval):
        """feval-only rows for one eval set (idx -1 = training), no
        builtin metrics — lets the train loop add feval results without
        re-running every builtin metric per valid set."""
        if feval is None:
            return []
        if idx < 0:
            # boosters loaded from model text have no training score
            if self._gbdt._train_score is None:
                return []
            score = np.asarray(self._gbdt._train_score, np.float64)
            dataset = self.train_set
        else:
            score = self._gbdt.valid_score_host(idx)
            dataset = (self.valid_sets_py[idx]
                       if getattr(self, "valid_sets_py", None) else None)
        s = score[0] if self._gbdt.num_tree_per_iteration == 1 else score
        res = feval(s, dataset)
        if isinstance(res, tuple):
            res = [res]
        return [(name, mname, val, hib) for mname, val, hib in res]

    # ------------------------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        # reference default: None -> best_iteration (all trees when no
        # early stopping set one, since best_iteration is then -1)
        if num_iteration is None:
            num_iteration = self.best_iteration
        if isinstance(data, str):
            # predict straight from a data file (reference Booster.predict
            # accepts a filename; role columns honored via params)
            from .io.loader import detect_file_format, load_file
            fmt = detect_file_format(data)
            data = load_file(data, Config.from_params(
                dict(self.params or {}, **kwargs)))[0]
            if (fmt == "libsvm" and data.ndim == 2
                    and data.shape[1] < self.num_feature()):
                # ONLY LibSVM: its width is the max index SEEN, so trailing
                # all-zero model features may be absent.  Dense formats
                # must keep the shape check (a pad would silently mask a
                # missing column as zeros)
                data = np.pad(data,
                              ((0, 0),
                               (0, self.num_feature() - data.shape[1])))
        from .io.dataset import _is_dataframe, _is_sparse
        if _is_dataframe(data):
            from .io.dataset import _pandas_to_numpy, _require_pandas_mapping
            pc = getattr(self, "pandas_categorical", None)
            _require_pandas_mapping(data, pc, "prediction DataFrame")
            # re-code category columns against the TRAINING category lists
            # (unseen values -> NaN), like the reference's predictor
            data = _pandas_to_numpy(data, "auto", pc)[0]
        elif hasattr(data, "values"):
            data = data.values
        in_fmt = getattr(data, "format", None) if _is_sparse(data) else None
        if _is_sparse(data):   # scipy.sparse: block-densified predict
            data = data.tocsr()
        else:
            data = np.asarray(data, dtype=np.float64)
        n_feat = self.num_feature()
        data_feat = data.shape[1] if data.ndim == 2 else data.shape[0]
        if data_feat != n_feat and not kwargs.get("predict_disable_shape_check", False):
            raise LightGBMError(
                f"The number of features in data ({data_feat}) is not the same "
                f"as it was in training data ({n_feat}).\n"
                "You can set ``predict_disable_shape_check=true`` to discard this error")
        if pred_leaf:
            return self._gbdt.predict_leaf_index(data, num_iteration)
        if pred_contrib:
            # sparse-in -> sparse-out (input format preserved), like the
            # reference python package's LGBM_BoosterPredictSparseOutput
            return self._gbdt.predict_contrib(
                data, num_iteration, start_iteration,
                sparse=in_fmt is not None, sparse_format=in_fmt)
        return self._gbdt.predict(data, num_iteration, start_iteration, raw_score)

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: Optional[str] = None) -> "Booster":
        with open(filename, "w") as f:
            f.write(self.model_to_string(num_iteration, start_iteration, importance_type))
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: Optional[str] = None) -> str:
        if importance_type is None:
            # reference: saved_feature_importance_type picks the stored kind
            importance_type = ("gain" if int(self.params.get(
                "saved_feature_importance_type", 0)) == 1 else "split")
        if num_iteration is None:
            num_iteration = self.best_iteration      # reference default
        text = model_io.save_model_to_string(
            self._gbdt, num_iteration, start_iteration,
            1 if importance_type == "gain" else 0)
        # trailing pandas_categorical line exactly like the reference
        # python package appends (basic.py _dump_pandas_categorical:445);
        # the reference C++ text parser ignores it, so interop is kept
        return text + model_io.format_pandas_categorical(
            getattr(self, "pandas_categorical", None))

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0) -> dict:
        g = self._gbdt
        K = g.num_tree_per_iteration
        with get_tracer().span("lgbm/dump"):
            models = g.models       # drains: builds the host trees
            return self._dump_dict(g, K, models)

    def _dump_dict(self, g, K, models) -> dict:
        return {
            "name": "tree",
            "version": "v3",
            "num_class": g.num_class,
            "num_tree_per_iteration": K,
            "label_index": 0,
            "max_feature_idx": g.max_feature_idx,
            "objective": g.config.objective,
            "feature_names": (g.train_data.feature_names if g.train_data else []),
            # reference dump carries the pandas category lists too
            # (Booster.dump_model, python-package/lightgbm/basic.py)
            "pandas_categorical": getattr(self, "pandas_categorical", None),
            "tree_info": [dict(tree_index=i, **t.to_json()) for i, t in enumerate(models)],
        }

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type, iteration or -1)

    def feature_name(self) -> List[str]:
        if self._gbdt.train_data is not None:
            return list(self._gbdt.train_data.feature_names)
        return list(getattr(self._gbdt, "feature_names_", []))

    def get_split_value_histogram(self, feature, bins=None,
                                  xgboost_style: bool = False):
        """Histogram of a feature's real split thresholds across the model
        (reference ``basic.py:3164``)."""
        if isinstance(feature, str):
            names = self.feature_name()
            if feature not in names:
                raise LightGBMError(f"Unknown feature name {feature!r}")
            feature = names.index(feature)
        values = []
        for t in self._gbdt.models:
            for j in range(t.num_internal):
                if (int(t.split_feature[j]) == feature
                        and not t.is_categorical_split(j)):
                    values.append(float(t.threshold[j]))
        values = np.array(values, dtype=np.float64)
        n_unique = len(np.unique(values))
        if bins is None or (isinstance(bins, int) and bins > n_unique):
            bins = max(n_unique, 1)
        hist, bin_edges = np.histogram(values, bins=bins)
        if xgboost_style:
            ret = np.column_stack((bin_edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            try:
                import pandas as pd
                return pd.DataFrame(ret, columns=["SplitValue", "Count"])
            except ImportError:
                return ret
        return hist, bin_edges

    def trees_to_dataframe(self):
        """Flatten the model into one row per node (reference ``basic.py:2245``)."""
        import pandas as pd
        if self.num_trees() == 0:
            raise LightGBMError("There are no trees in this Booster and thus nothing to parse")

        names = self.feature_name()

        def node_rows(tree_index, node, depth, parent):
            if "split_index" in node:
                name = f"{tree_index}-S{node['split_index']}"
                feat_idx = node["split_feature"]
                feat = names[feat_idx] if feat_idx < len(names) else f"Column_{feat_idx}"
                left = node["left_child"]
                right = node["right_child"]

                def child_name(c):
                    return (f"{tree_index}-S{c['split_index']}" if "split_index" in c
                            else f"{tree_index}-L{c['leaf_index']}")
                rows = [{
                    "tree_index": tree_index, "node_depth": depth,
                    "node_index": name,
                    "left_child": child_name(left), "right_child": child_name(right),
                    "parent_index": parent, "split_feature": feat,
                    "split_gain": node["split_gain"], "threshold": node["threshold"],
                    "decision_type": node["decision_type"],
                    "missing_direction": "left" if node["default_left"] else "right",
                    "missing_type": node["missing_type"],
                    "value": node["internal_value"], "weight": None,
                    "count": node["internal_count"]}]
                rows += node_rows(tree_index, left, depth + 1, name)
                rows += node_rows(tree_index, right, depth + 1, name)
                return rows
            name = f"{tree_index}-L{node.get('leaf_index', 0)}"
            return [{
                "tree_index": tree_index, "node_depth": depth,
                "node_index": name, "left_child": None, "right_child": None,
                "parent_index": parent, "split_feature": None,
                "split_gain": None, "threshold": None, "decision_type": None,
                "missing_direction": None, "missing_type": None,
                "value": node["leaf_value"],
                "weight": node.get("leaf_weight"),
                "count": node.get("leaf_count", 0)}]

        model = self.dump_model()
        rows = []
        for ti in model["tree_info"]:
            rows += node_rows(ti["tree_index"], ti["tree_structure"], 1, None)
        return pd.DataFrame(rows, columns=[
            "tree_index", "node_depth", "node_index", "left_child",
            "right_child", "parent_index", "split_feature", "split_gain",
            "threshold", "decision_type", "missing_direction", "missing_type",
            "value", "weight", "count"])

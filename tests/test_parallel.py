"""Distributed-training tests on the 8-device virtual CPU mesh
(the analog of the reference's local-cluster Dask tests, ``test_dask.py``:
real collectives, no mock backend)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.medium

from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel import default_mesh, make_dp_train_step
from lightgbm_tpu.parallel.data_parallel import shard_rows


def _cfg(num_leaves=15, max_bin=32, axis_name=None):
    sp = SplitParams(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=5,
                     min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                     max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
                     cat_l2=10.0, max_cat_to_onehot=4)
    return GrowerConfig(num_leaves=num_leaves, max_depth=-1, max_bin=max_bin,
                        split=sp, feature_fraction_bynode=1.0,
                        hist_method="scatter", hist_chunk_rows=65536,
                        axis_name=axis_name)


def _meta(n_feat, max_bin):
    return dict(num_bins=jnp.full(n_feat, max_bin, jnp.int32),
                default_bins=jnp.zeros(n_feat, jnp.int32),
                nan_bins=jnp.full(n_feat, -1, jnp.int32),
                is_categorical=jnp.zeros(n_feat, bool),
                monotone=jnp.zeros(n_feat, jnp.int32))


def _data(n, f, max_bin, seed=0):
    rng = np.random.default_rng(seed)
    bins = rng.integers(0, max_bin - 1, size=(n, f), dtype=np.uint8)
    logit = (bins[:, 0].astype(np.float32) - max_bin / 2
             + 0.5 * bins[:, 1].astype(np.float32))
    label = (logit + 4 * rng.logistic(size=n) > 0).astype(np.float32)
    return bins, label


def _grad_fn(score, label, weight=None):
    y = jnp.where(label > 0, 1.0, -1.0)
    resp = -y / (1.0 + jnp.exp(y * score))
    g, h = resp, jnp.abs(resp) * (1.0 - jnp.abs(resp))
    if weight is not None:
        g, h = g * weight, h * weight
    return g, h


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_dp_tree_matches_single_device():
    """Sharded growth must produce the exact same tree as single-device
    (the reference's distributed-vs-single parity expectation,
    ``test_dask.py`` model-quality comparison, but exact here)."""
    n, f, max_bin = 512, 6, 32
    bins_np, label_np = _data(n, f, max_bin)
    meta = _meta(f, max_bin)
    key = jax.random.key(3)

    # single device reference
    g, h = _grad_fn(jnp.zeros(n), jnp.asarray(label_np))
    tree_ref, assign_ref = jax.jit(
        lambda b, g, h: grow_tree(b, g, h, jnp.ones(n), jnp.ones(f),
                                  meta["num_bins"], meta["default_bins"],
                                  meta["nan_bins"], meta["is_categorical"],
                                  meta["monotone"], key, _cfg()))(
        jnp.asarray(bins_np), g, h)

    # 8-way data parallel
    mesh = default_mesh(8)
    step = make_dp_train_step(_cfg(axis_name="data"), meta, _grad_fn,
                              learning_rate=0.1, mesh=mesh)
    sh = shard_rows(mesh)
    bins = jax.device_put(jnp.asarray(bins_np), sh)
    label = jax.device_put(jnp.asarray(label_np), sh)
    score = jax.device_put(jnp.zeros(n, jnp.float32), sh)
    rw = jax.device_put(jnp.ones(n, jnp.float32), sh)
    new_score, tree_dp = step(bins, label, score, rw, jnp.ones(f), key)

    assert int(tree_dp.num_leaves) == int(tree_ref.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree_dp.split_feature),
                                  np.asarray(tree_ref.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_dp.threshold),
                                  np.asarray(tree_ref.threshold))
    np.testing.assert_allclose(np.asarray(tree_dp.leaf_value),
                               np.asarray(tree_ref.leaf_value),
                               rtol=1e-4, atol=1e-5)
    # score update must equal single-device scoring
    expected = np.asarray(tree_ref.leaf_value)[np.asarray(assign_ref)] * 0.1
    np.testing.assert_allclose(np.asarray(new_score), expected,
                               rtol=1e-4, atol=1e-5)


def test_dp_multiple_iterations_improve_loss():
    n, f, max_bin = 1024, 6, 32
    bins_np, label_np = _data(n, f, max_bin, seed=5)
    meta = _meta(f, max_bin)
    mesh = default_mesh(8)
    step = make_dp_train_step(_cfg(axis_name="data"), meta, _grad_fn,
                              learning_rate=0.2, mesh=mesh)
    sh = shard_rows(mesh)
    bins = jax.device_put(jnp.asarray(bins_np), sh)
    label = jax.device_put(jnp.asarray(label_np), sh)
    score = jax.device_put(jnp.zeros(n, jnp.float32), sh)
    rw = jax.device_put(jnp.ones(n, jnp.float32), sh)

    def logloss(s):
        p = 1 / (1 + np.exp(-np.asarray(s)))
        y = label_np
        return -np.mean(y * np.log(p + 1e-9) + (1 - y) * np.log(1 - p + 1e-9))

    l0 = logloss(score)
    for i in range(10):
        score, tree = step(bins, label, score, rw, jnp.ones(f),
                           jax.random.key(i))
    l1 = logloss(score)
    assert l1 < l0 - 0.05, (l0, l1)


def test_fp_tree_matches_single_device():
    """Feature-parallel growth (features sharded, rows replicated) produces
    the identical tree (FeatureParallelTreeLearner semantics: same data,
    sharded search, allreduce-max of the SplitInfo)."""
    from lightgbm_tpu.parallel import make_fp_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P
    n, f, max_bin = 512, 8, 32            # f divisible by 8
    bins_np, label_np = _data(n, f, max_bin, seed=11)
    meta = _meta(f, max_bin)
    key = jax.random.key(7)

    g, h = _grad_fn(jnp.zeros(n), jnp.asarray(label_np))
    tree_ref, assign_ref = jax.jit(
        lambda b, g, h: grow_tree(b, g, h, jnp.ones(n), jnp.ones(f),
                                  meta["num_bins"], meta["default_bins"],
                                  meta["nan_bins"], meta["is_categorical"],
                                  meta["monotone"], key, _cfg()))(
        jnp.asarray(bins_np), g, h)

    mesh = default_mesh(8, axis_name="feature")
    step = make_fp_train_step(_cfg(), meta, _grad_fn, learning_rate=0.1,
                              mesh=mesh)
    sh = NamedSharding(mesh, P(None, "feature"))
    bins = jax.device_put(jnp.asarray(bins_np), sh)
    new_score, tree_fp = step(bins, jnp.asarray(label_np),
                              jnp.zeros(n, jnp.float32),
                              jnp.ones(n, jnp.float32), jnp.ones(f), key)

    assert int(tree_fp.num_leaves) == int(tree_ref.num_leaves)
    np.testing.assert_array_equal(np.asarray(tree_fp.split_feature),
                                  np.asarray(tree_ref.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_fp.threshold),
                                  np.asarray(tree_ref.threshold))
    np.testing.assert_allclose(np.asarray(tree_fp.leaf_value),
                               np.asarray(tree_ref.leaf_value),
                               rtol=1e-4, atol=1e-5)
    expected = np.asarray(tree_ref.leaf_value)[np.asarray(assign_ref)] * 0.1
    np.testing.assert_allclose(np.asarray(new_score), expected,
                               rtol=1e-4, atol=1e-5)


def test_voting_parallel_learns():
    """Voting-parallel training converges; with top_k >= F the vote elects
    every feature, so the tree matches single-device exactly."""
    from lightgbm_tpu.parallel import make_voting_train_step
    n, f, max_bin = 1024, 6, 32
    bins_np, label_np = _data(n, f, max_bin, seed=13)
    meta = _meta(f, max_bin)
    mesh = default_mesh(8)
    key = jax.random.key(2)

    # exactness check when every feature is elected
    g, h = _grad_fn(jnp.zeros(n), jnp.asarray(label_np))
    tree_ref, _ = jax.jit(
        lambda b, g, h: grow_tree(b, g, h, jnp.ones(n), jnp.ones(f),
                                  meta["num_bins"], meta["default_bins"],
                                  meta["nan_bins"], meta["is_categorical"],
                                  meta["monotone"], key, _cfg()))(
        jnp.asarray(bins_np), g, h)
    step_all = make_voting_train_step(_cfg(), meta, _grad_fn, 0.2, mesh,
                                      top_k=f)
    sh = shard_rows(mesh)
    bins = jax.device_put(jnp.asarray(bins_np), sh)
    label = jax.device_put(jnp.asarray(label_np), sh)
    score = jax.device_put(jnp.zeros(n, jnp.float32), sh)
    rw = jax.device_put(jnp.ones(n, jnp.float32), sh)
    _, tree_v = step_all(bins, label, score, rw, jnp.ones(f), key)
    np.testing.assert_array_equal(np.asarray(tree_v.split_feature),
                                  np.asarray(tree_ref.split_feature))
    np.testing.assert_array_equal(np.asarray(tree_v.threshold),
                                  np.asarray(tree_ref.threshold))

    # restricted vote (top_k=2 -> 4 elected of 6) still converges
    step = make_voting_train_step(_cfg(), meta, _grad_fn, 0.2, mesh, top_k=2)

    def logloss(s):
        p = 1 / (1 + np.exp(-np.asarray(s)))
        y = label_np
        return -np.mean(y * np.log(p + 1e-9) + (1 - y) * np.log(1 - p + 1e-9))

    l0 = logloss(score)
    for i in range(10):
        score, _ = step(bins, label, score, rw, jnp.ones(f), jax.random.key(i))
    l1 = logloss(score)
    assert l1 < l0 - 0.05, (l0, l1)


def test_graft_entry_dryrun():
    import importlib.util, pathlib
    spec = importlib.util.spec_from_file_location(
        "graft_entry", pathlib.Path(__file__).parent.parent / "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert int(out[1]) > 1
    mod.dryrun_multichip(8)


# ---------------------------------------------------------------------------
# public-API routing: lgb.train({"tree_learner": ...}) must use the mesh
# (reference CreateTreeLearner factory, tree_learner.cpp:15-53)

def _api_data(n=1000, f=8, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.3 * rng.normal(size=n) > 0.3)
    return X, y.astype(np.float64)


def _api_train(tree_learner, X, y, **extra):
    import lightgbm_tpu as lgb
    params = {"objective": "binary", "num_leaves": 15, "min_data_in_leaf": 5,
              "max_bin": 63, "verbose": -1, "tree_learner": tree_learner,
              "seed": 7}
    params.update(extra)
    ds = lgb.Dataset(X, label=y, params=params)
    return lgb.train(params, ds, num_boost_round=5)


@pytest.mark.parametrize("learner,extra", [
    pytest.param("data", {}, id="data"),
    pytest.param("feature", {}, id="feature"),
    pytest.param("voting", {}, id="voting"),
    # the table in 4 shards on a 4-device mesh, at the library's leaf limits
    # (at 31 leaves the last splits of these 1,001 rows gain under 1e-5,
    # float noise whose order any change of summation order flips)
    pytest.param("data", {"shards": 4, "mesh_shape": [4], "min_data_in_leaf": 20,
                          "min_sum_hessian_in_leaf": 1e-3}, id="data-4-shards")])
def test_api_tree_learner_matches_serial(learner, extra):
    """Through the PUBLIC API, every parallel learner on the 8-device mesh
    must produce the identical model to serial training (stronger than the
    reference's quality-only Dask parity, test_dask.py)."""
    # odd n exercises the data-mode row-pad path; feature mode replicates
    # rows (f=8 divides the mesh, nothing pads either way).  n=1000 at this
    # seed is avoided deliberately: that dataset has a genuine split-gain
    # near-tie (two splits equal to 6 digits) which the sharded learners'
    # different float-reduction order can legitimately flip — the exact-
    # structure assertion below is only meaningful on tie-free data.
    X, y = _api_data(n=1001)
    extra = dict(extra)
    shards = extra.pop("shards", 0)
    serial = _api_train("serial", X, y, **{k: v for k, v in extra.items()
                                            if k != "mesh_shape"})
    par = _api_train(learner, np.array_split(X, shards) if shards else X, y,
                     **extra)
    if shards:
        assert par._gbdt._mesh.devices.size == 4
        assert par._gbdt._grower_name() == "frontier"
        # leaf counts exact (below); each leaf value is its own float32 sums
        # of gradient and hessian over its rows, added across 4 shards in
        # another order than on one device: equal to a few float32 ulps
        for ts, tp in zip(serial.dump_model()["tree_info"],
                          par.dump_model()["tree_info"]):
            np.testing.assert_allclose(_leaf_values(tp), _leaf_values(ts),
                                       rtol=1e-5, atol=1e-7)
    assert serial.num_trees() == par.num_trees()
    np.testing.assert_allclose(par.predict(X), serial.predict(X),
                               rtol=0, atol=1e-6)
    # identical tree STRUCTURE (features, thresholds, topology, counts);
    # float-valued lines (gains, leaf values) may differ in final ulps from
    # collective reduction order
    struct_keys = ("split_feature=", "threshold=", "left_child=",
                   "right_child=", "leaf_count=")

    def structure(s):
        return [l for l in s.splitlines() if l.startswith(struct_keys)]
    assert structure(par.model_to_string()) == structure(serial.model_to_string())


def _leaf_values(tree_json):
    out, stack = {}, [tree_json["tree_structure"]]
    while stack:
        node = stack.pop()
        if "leaf_index" in node:
            out[node["leaf_index"]] = node["leaf_value"]
        else:
            stack += [node["left_child"], node["right_child"]]
    return np.array([out[i] for i in sorted(out)])


def test_shards_bin_as_the_whole_table(n=1003):
    """A table given as a list of shards (rows that do not divide the mesh)
    gets the bin mappers and bins of the whole matrix, byte for byte, and
    under ``tree_learner=data`` its bins go to the mesh padded to a multiple
    of it, each device holding its own rows; a validation set's scores are
    padded alike and read back without the padding."""
    import lightgbm_tpu as lgb
    X, y = _api_data(n=n)
    Xv, yv = _api_data(n=n // 2, seed=6)
    params = {"objective": "binary", "max_bin": 63, "verbose": -1,
              "tree_learner": "data", "mesh_shape": [4], "num_leaves": 7}
    whole = lgb.Dataset(X, label=y, params=params).construct()._inner
    sharded = lgb.Dataset(np.array_split(X, 4), label=y, params=params)
    inner = sharded.construct()._inner
    assert ([m.to_state() for m in inner.bin_mappers]
            == [m.to_state() for m in whole.bin_mappers])
    assert inner.bins.tobytes() == whole.bins.tobytes()
    bst = lgb.Booster(params, sharded)
    valid = lgb.Dataset(np.array_split(Xv, 4), label=yv, reference=sharded)
    bst.add_valid(valid, "v")
    bins = bst._gbdt._dd.bins
    assert bins.shape[0] == -(-n // 4) * 4
    assert {s.data.shape[0] for s in bins.addressable_shards} == {-(-n // 4)}
    assert len({s.device for s in bins.addressable_shards}) == 4
    bst.update()
    vs = bst._gbdt._valid_scores[0]
    assert vs.shape[1] == -(-(n // 2) // 4) * 4
    np.testing.assert_allclose(bst._gbdt.valid_score_host(0)[0],
                               bst.predict(Xv, raw_score=True), rtol=1e-6, atol=1e-6)


def test_frontier_shards_grow_one_tree_bit_for_bit():
    """Under ``tree_learner=data`` nothing but the histograms crosses the
    mesh: each shard searches the exchanged sums by itself.  Every shard's
    tree, split records and leaf values alike, is the same bit for bit."""
    from jax.sharding import PartitionSpec as P
    from lightgbm_tpu.ops.frontier import grow_tree_frontier
    n, f, max_bin = 1000, 6, 32
    bins_np, label_np = _data(n, f, max_bin, seed=2)
    g, h = _grad_fn(jnp.zeros(n), jnp.asarray(label_np))
    meta = _meta(f, max_bin)
    cfg = _cfg(axis_name="data")._replace(parallel_mode="data", num_shards=4,
                                          frontier_k=4)

    def grow(b, g, h, rw):
        tree, _ = grow_tree_frontier(
            b, g, h, rw, jnp.ones(f, bool), meta["num_bins"],
            meta["default_bins"], meta["nan_bins"], meta["is_categorical"],
            meta["monotone"], jax.random.key(1), cfg)
        return jax.tree.map(lambda a: a[None], tree)

    per_shard = jax.jit(jax.shard_map(
        grow, mesh=default_mesh(4), in_specs=(P("data"),) * 4,
        out_specs=P("data"), check_vma=False))(
        jnp.asarray(bins_np), g, h, jnp.ones(n, jnp.float32))
    assert int(per_shard.num_leaves[0]) == cfg.num_leaves
    for name, a in zip(per_shard._fields, per_shard):
        a = np.asarray(a)
        for s in range(1, 4):
            assert a[s].tobytes() == a[0].tobytes(), (name, s)


def test_api_tree_learner_uses_mesh():
    X, y = _api_data()
    bst = _api_train("data", X, y)
    assert bst._gbdt._mesh is not None
    assert bst._gbdt._grower_cfg.parallel_mode == "data"


def test_api_tree_learner_bagging_parity():
    """Bagging + data-parallel must match serial bagging exactly (the
    bagging mask is computed globally, then sharded)."""
    X, y = _api_data(n=999)
    kw = dict(bagging_fraction=0.7, bagging_freq=1, bagging_seed=11)
    serial = _api_train("serial", X, y, **kw)
    par = _api_train("data", X, y, **kw)
    np.testing.assert_allclose(par.predict(X), serial.predict(X),
                               rtol=0, atol=1e-6)

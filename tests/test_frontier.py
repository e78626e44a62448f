"""Frontier (round-batched best-first) grower vs the sequential grower.

The frontier grower (``ops/frontier.py``) must produce IDENTICAL models to
the one-split-at-a-time loop — same splits, same numbering (pred_leaf), same
values — whenever it is eligible; ineligible feature combos must fall back
to the sequential grower transparently.
"""
import numpy as np
import pytest
from sklearn.datasets import make_classification, make_regression

import lightgbm_tpu as lgb

pytestmark = pytest.mark.medium


def _models(params, X, y, rounds=4, **dskw):
    out = []
    for grower in ("serial", "frontier"):
        p = dict(params, tree_grower=grower, verbose=-1)
        ds = lgb.Dataset(X, label=y, params=p, **dskw)
        out.append(lgb.train(p, ds, num_boost_round=rounds))
    return out


def _assert_identical(bs, bf, X):
    np.testing.assert_array_equal(bs.predict(X, pred_leaf=True),
                                  bf.predict(X, pred_leaf=True))
    np.testing.assert_allclose(bs.predict(X), bf.predict(X), rtol=1e-6,
                               atol=1e-9)


@pytest.fixture(scope="module")
def clf_data():
    X, y = make_classification(n_samples=1500, n_features=12,
                               n_informative=7, random_state=7)
    return X.astype(np.float32), y


@pytest.mark.parametrize("k", [1, 3, 16])
def test_binary_parity_across_batch_sizes(clf_data, k):
    X, y = clf_data
    bs, bf = _models({"objective": "binary", "num_leaves": 31,
                      "min_data_in_leaf": 5, "frontier_k": k}, X, y)
    _assert_identical(bs, bf, X)


def test_regression_weighted_parity():
    X, y = make_regression(n_samples=1200, n_features=8, noise=4.0,
                           random_state=3)
    X = X.astype(np.float32)
    w = np.abs(np.random.default_rng(0).normal(1.0, 0.4, len(y))) + 0.1
    out = []
    for grower in ("serial", "frontier"):
        p = {"objective": "regression", "num_leaves": 24, "verbose": -1,
             "tree_grower": grower}
        ds = lgb.Dataset(X, label=y, weight=w, params=p)
        out.append(lgb.train(p, ds, num_boost_round=4))
    _assert_identical(*out, X)


def test_multiclass_goss_parity():
    # needs genuinely separable classes: threshold-constructed labels give
    # near-zero-gain tie splits whose resolution legitimately differs with
    # histogram float-summation order, which GOSS's gradient-driven
    # resampling then amplifies — on real multiclass data parity is exact
    X, y = make_classification(n_samples=2000, n_features=12,
                               n_informative=8, n_classes=3,
                               n_clusters_per_class=2, random_state=2)
    X = X.astype(np.float32)
    bs, bf = _models({"objective": "multiclass", "num_class": 3,
                      "num_leaves": 15, "boosting": "goss",
                      "min_data_in_leaf": 10}, X, y)
    _assert_identical(bs, bf, X)


def test_categorical_parity(clf_data):
    X, y = clf_data
    Xc = X.copy()
    Xc[:, 0] = np.floor(np.abs(Xc[:, 0]) * 7) % 12       # 12 categories
    bs, bf = _models({"objective": "binary", "num_leaves": 31,
                      "max_cat_to_onehot": 4}, Xc, y,
                     categorical_feature=[0])
    _assert_identical(bs, bf, Xc)


def test_categorical_many_words_partition():
    # 200 categories: a split's bit set spans seven 32-bit words, so the
    # partition has to pick the word of each row's bin, not only word 0.
    # The rows the grower put in a leaf are the rows the model sends there.
    rng = np.random.default_rng(3)
    X = rng.normal(size=(3000, 6)).astype(np.float32)
    X[:, 0] = rng.integers(0, 200, len(X))
    good = rng.random(200) < 0.5
    y = (good[X[:, 0].astype(int)]
         ^ (rng.random(len(X)) < 0.1)).astype(np.float64)
    p = {"objective": "binary", "num_leaves": 31, "max_cat_to_onehot": 4,
         "min_data_per_group": 5, "cat_smooth": 1.0, "max_cat_threshold": 128,
         "min_data_in_leaf": 5, "tree_grower": "frontier", "verbose": -1}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p,
                                   categorical_feature=[0]), 2)
    words = [int(w) for line in bst.model_to_string().splitlines()
             if line.startswith("cat_threshold=")
             for w in line.split("=")[1].split()]
    assert sum(w != 0 for w in words) > 8

    def leaf_counts(node, out):
        if "leaf_index" in node:
            out[node["leaf_index"]] = node["leaf_count"]
        else:
            leaf_counts(node["left_child"], out)
            leaf_counts(node["right_child"], out)
        return out

    leaves = bst.predict(X, pred_leaf=True)
    for t, info in enumerate(bst.dump_model()["tree_info"]):
        said = leaf_counts(info["tree_structure"], {})
        got = np.bincount(leaves[:, t], minlength=len(said))
        assert {i: int(c) for i, c in enumerate(got)} == said


def test_max_depth_and_bagging_parity(clf_data):
    X, y = clf_data
    bs, bf = _models({"objective": "binary", "num_leaves": 63, "max_depth": 4,
                      "bagging_fraction": 0.6, "bagging_freq": 1,
                      "bagging_seed": 9}, X, y)
    _assert_identical(bs, bf, X)


def test_ineligible_falls_back(clf_data):
    # monotone intermediate/advanced propagate bounds ACROSS leaves (split-
    # order coupled): frontier must transparently take the sequential
    # grower and still train (basic mode is served natively, see below)
    X, y = clf_data
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "tree_grower": "frontier",
         "monotone_constraints_method": "intermediate",
         "monotone_constraints": [1] + [0] * (X.shape[1] - 1)}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)
    assert bst.num_trees() == 3


# ---------------------------------------------------------------------------
# monotone-basic served by the frontier (ROADMAP item 5a): bounds pinch at
# the midpoint down the root path — exactly the per-leaf state the frontier
# tracks, so parity with the sequential grower must be exact
@pytest.fixture(scope="module")
def mono_data():
    rng = np.random.default_rng(0)
    n = 3000
    X = rng.uniform(-2, 2, (n, 4)).astype(np.float32)
    y = (1.5 * X[:, 0] + np.sin(2 * X[:, 1]) + 0.3 * X[:, 2] ** 2
         - 0.8 * X[:, 3] + rng.normal(0, 0.2, n))
    return X, y


@pytest.mark.parametrize("extra", [
    {},                                          # plain basic bounds
    {"monotone_penalty": 1.5},                   # + depth-scaled penalty
    {"max_depth": 5, "frontier_k": 4},           # + depth gate, small batch
])
def test_monotone_basic_parity(mono_data, extra):
    X, y = mono_data
    bs, bf = _models({"objective": "regression", "num_leaves": 31,
                      "monotone_constraints": [1, 0, 0, -1], **extra},
                     X, y, rounds=5)
    _assert_identical(bs, bf, X)


def test_monotone_basic_frontier_is_monotone(mono_data):
    X, y = mono_data
    p = {"objective": "regression", "num_leaves": 63, "verbose": -1,
         "monotone_constraints": [1, 0, 0, -1], "tree_grower": "frontier"}
    bst = lgb.train(p, lgb.Dataset(X, label=y, params=p), 15)
    from tests.test_constraints import _monotone_violation
    assert _monotone_violation(bst, X, 0, +1) <= 1e-10
    assert _monotone_violation(bst, X, 3, -1) <= 1e-10


def test_sparse_efb_parity():
    import scipy.sparse as sp
    rng = np.random.default_rng(5)
    X = sp.random(1200, 40, density=0.06, random_state=5, format="csr",
                  dtype=np.float32)
    y = (np.asarray(X.sum(axis=1)).ravel() + rng.normal(0, .3, 1200)
         > 0.4).astype(np.float64)
    out = []
    for grower in ("serial", "frontier"):
        p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
             "tree_grower": grower, "min_data_in_leaf": 3}
        ds = lgb.Dataset(X, label=y, params=p)
        out.append(lgb.train(p, ds, num_boost_round=3))
    bs, bf = out
    Xd = np.asarray(X.todense())
    _assert_identical(bs, bf, Xd)


_INTERPRET_CHECK = r"""
import numpy as np, jax.numpy as jnp
from unittest import mock
import jax.experimental.pallas as pl
import lightgbm_tpu.ops.histogram as H

rng = np.random.default_rng(0)
BR, NB, NC, B, k = 128, 6, 10, 64, 3
C = BR * NB
comb = rng.integers(0, B, size=(C, NC)).astype(np.uint8)
g = rng.normal(size=C).astype(np.float32)
h = rng.random(C).astype(np.float32)
m = (rng.random(C) > 0.2).astype(np.float32)
bl = np.sort(rng.integers(0, k, size=NB)).astype(np.int32)
ref = H.fold_hist(H.build_histogram_leaves(
    jnp.asarray(comb), jnp.asarray(g), jnp.asarray(h), jnp.asarray(m),
    jnp.asarray(bl), k, B, method="scatter", block_rows=BR, f_limit=8))
orig = pl.pallas_call
def interp(*a, **kw):
    kw["interpret"] = True
    return orig(*a, **kw)
with mock.patch.object(pl, "pallas_call", interp):
    got = H.fold_hist(H._hist_leaves_pallas(
        jnp.asarray(comb), jnp.asarray(g), jnp.asarray(h),
        jnp.asarray(m), jnp.asarray(bl), k, B, BR, 8))
np.testing.assert_allclose(np.asarray(ref)[:, :8], np.asarray(got),
                           atol=1e-3)
print("INTERPRET_OK")
"""


def test_batched_hist_kernel_interpret_parity():
    # the Pallas batched-leaf kernel vs the scatter fallback, in interpret
    # mode, in a clean subprocess.  (The real TPU lowering is covered by
    # tests/test_chip_smoke.py's AOT compile and, on the chip, by
    # chip_smoke.py phase 1.)
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if "PYTHONPATH" not in k}
    env["PYTHONPATH"] = repo
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _INTERPRET_CHECK], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "INTERPRET_OK" in r.stdout, r.stdout + r.stderr


def test_data_parallel_frontier_parity(clf_data):
    # rows sharded over an 8-device CPU mesh must reproduce the serial
    # frontier model (same splits through psum'd histograms)
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    X, y = clf_data
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 5, "tree_learner": "data"}
    ds = lgb.Dataset(X, label=y, params=p)
    bd = lgb.train(p, ds, num_boost_round=3)
    p2 = {"objective": "binary", "num_leaves": 31, "verbose": -1,
          "min_data_in_leaf": 5}
    bs = lgb.train(p2, lgb.Dataset(X, label=y, params=p2), num_boost_round=3)
    np.testing.assert_allclose(bs.predict(X), bd.predict(X), rtol=1e-4,
                               atol=1e-6)


@pytest.mark.parametrize("learner", ["feature", "voting"])
def test_parallel_mode_frontier_parity(clf_data, learner):
    # feature- and voting-parallel over the 8-device mesh must engage the
    # frontier grower and reproduce the serial model
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    import lightgbm_tpu.ops.frontier as F
    X, y = clf_data
    calls = {"n": 0}
    orig = F.grow_tree_frontier

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    F.grow_tree_frontier = spy
    try:
        p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
             "min_data_in_leaf": 5, "tree_learner": learner}
        bp = lgb.train(p, lgb.Dataset(X, label=y, params=p),
                       num_boost_round=3)
    finally:
        F.grow_tree_frontier = orig
    assert calls["n"] > 0
    ps = {"objective": "binary", "num_leaves": 31, "verbose": -1,
          "min_data_in_leaf": 5}
    bs = lgb.train(ps, lgb.Dataset(X, label=y, params=ps), num_boost_round=3)
    np.testing.assert_allclose(bp.predict(X), bs.predict(X), rtol=1e-5,
                               atol=1e-6)


def test_bynode_sampling_served_by_frontier(clf_data):
    """feature_fraction_bynode < 1 no longer falls back (VERDICT r4 item 7):
    the frontier serves it with a split-record-keyed RNG stream.  The stream
    legitimately differs from the serial grower's step-keyed one, so the
    contract is: deterministic, structurally valid, and comparably accurate."""
    from sklearn.metrics import roc_auc_score
    X, y = clf_data
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 5, "feature_fraction_bynode": 0.5, "seed": 11}

    def train(grower):
        pp = dict(p, tree_grower=grower)
        return lgb.train(pp, lgb.Dataset(X, label=y, params=pp),
                         num_boost_round=5)

    bf1, bf2 = train("frontier"), train("frontier")
    # deterministic: same seed -> identical model
    np.testing.assert_array_equal(bf1.predict(X, pred_leaf=True),
                                  bf2.predict(X, pred_leaf=True))
    # genuinely sampled: differs from the unsampled frontier model
    pp = {k: v for k, v in p.items() if k != "feature_fraction_bynode"}
    pp["tree_grower"] = "frontier"
    full = lgb.train(pp, lgb.Dataset(X, label=y, params=pp), num_boost_round=5)
    assert not np.array_equal(full.predict(X, pred_leaf=True),
                              bf1.predict(X, pred_leaf=True))
    # comparably accurate to the serial grower under the same config
    bs = train("serial")
    auc_f = roc_auc_score(y, bf1.predict(X))
    auc_s = roc_auc_score(y, bs.predict(X))
    assert auc_f > 0.9 and abs(auc_f - auc_s) < 0.03


def test_extra_trees_served_by_frontier(clf_data):
    from sklearn.metrics import roc_auc_score
    X, y = clf_data
    p = {"objective": "binary", "num_leaves": 31, "verbose": -1,
         "min_data_in_leaf": 5, "extra_trees": True, "extra_seed": 4,
         "seed": 11}

    def train(grower, **kw):
        pp = dict(p, tree_grower=grower, **kw)
        return lgb.train(pp, lgb.Dataset(X, label=y, params=pp),
                         num_boost_round=5)

    bf1, bf2 = train("frontier"), train("frontier")
    np.testing.assert_array_equal(bf1.predict(X, pred_leaf=True),
                                  bf2.predict(X, pred_leaf=True))
    # extra_seed moves the threshold stream
    bf3 = train("frontier", extra_seed=99)
    assert not np.array_equal(bf1.predict(X, pred_leaf=True),
                              bf3.predict(X, pred_leaf=True))
    bs = train("serial")
    auc_f = roc_auc_score(y, bf1.predict(X))
    auc_s = roc_auc_score(y, bs.predict(X))
    assert auc_f > 0.88 and abs(auc_f - auc_s) < 0.04


@pytest.mark.parametrize("learner", ["data", "voting", "feature"])
def test_bynode_extra_trees_parallel_frontier(clf_data, learner):
    """The re-keyed RNG paths compile and stay deterministic under ALL
    parallel learners on the virtual mesh (feature mode is the delicate
    one: shard-local rand thresholds + lslice'd per-node masks)."""
    X, y = clf_data
    nd = 2 if learner == "feature" else 4
    p = {"objective": "binary", "num_leaves": 15, "verbose": -1,
         "tree_grower": "frontier", "tree_learner": learner,
         "mesh_shape": [nd], "feature_fraction_bynode": 0.6,
         "extra_trees": True, "seed": 5, "min_data_in_leaf": 5}
    b1 = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)
    b2 = lgb.train(p, lgb.Dataset(X, label=y, params=p), num_boost_round=3)
    np.testing.assert_array_equal(b1.predict(X, pred_leaf=True),
                                  b2.predict(X, pred_leaf=True))
    assert b1.num_trees() == 3


# ---------------------------------------------------------------------------
# the [N]-pass rests on one invariant: a row carries its leaf slot, so what it
# needs of its leaf's split comes from comparing that slot with the selected
@pytest.mark.parametrize("layout", ["random", "whole"])
@pytest.mark.parametrize("k", [1, 3, 16])
def test_spread_by_slot_matches_gathers(k, layout):
    """_spread_by_slot and _bin_of_rows against the per-row gathers they stand
    for (``slot_of_leaf[row_leaf]``, then ``table[slot]`` and
    ``bins[row, col[slot]]``)."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.frontier import _bin_of_rows, _spread_by_slot
    n, LS, f = 997, 40, 7
    for seed in range(5):
        rng = np.random.default_rng(100 * k + seed)
        if layout == "whole":           # one leaf holds every row
            row_leaf = np.full(n, rng.integers(LS))
        else:                           # about half of the leaves are empty
            live = np.flatnonzero(rng.random(LS) < 0.5)
            live = live if len(live) else np.array([rng.integers(LS)])
            row_leaf = rng.choice(live, n)
        sel = rng.permutation(LS)
        valid = rng.random(k) < 0.7
        if layout == "whole":           # ... and a valid slot selects it
            whole = row_leaf[0]
            sel = np.concatenate([[whole], sel[sel != whole]])
            valid[0] = True
        sel = sel[:k]
        tables = (rng.integers(-5, 300, k).astype(np.int32),
                  rng.random(k) < 0.5,
                  rng.integers(0, 2 ** 31 - 1, k).astype(np.int32))
        bins = rng.integers(0, 256, (n, f)).astype(np.uint8)
        cols = rng.integers(0, f, k).astype(np.int32)

        slot_of_leaf = np.full(LS, -1)
        slot_of_leaf[sel[valid]] = np.arange(k)[valid]
        si = slot_of_leaf[row_leaf]
        want_act = si >= 0
        row_slot = jnp.asarray(row_leaf, jnp.int32)
        in_slot = [(row_slot == int(sel[i])) & bool(valid[i]) for i in range(k)]
        act, got = _spread_by_slot(in_slot, tuple(jnp.asarray(t) for t in tables))
        np.testing.assert_array_equal(np.asarray(act), want_act)
        for t, g in zip(tables, got):
            assert g.dtype == t.dtype
            np.testing.assert_array_equal(
                np.asarray(g), np.where(want_act, t[np.maximum(si, 0)], 0))
        colv = _bin_of_rows(jnp.asarray(bins).T, jnp.asarray(cols), in_slot)
        assert colv.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(colv),
            np.where(want_act, bins[np.arange(n), cols[np.maximum(si, 0)]], 0))


@pytest.mark.parametrize("k", [1, 3, 16])
def test_grouped_rows_are_the_stable_partitions_ranges(k):
    """_group_smaller_children against a NumPy stable partition: with the rows
    kept grouped by leaf as a stable partition from ``arange(n)`` keeps them,
    split every selected leaf's range in place; the smaller child's range is,
    child by child and in order, what the one sort puts in front, and its
    length the count the sort's keys give."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.frontier import _group_smaller_children
    n, LS = 1203, 24
    for seed in range(4):
        rng = np.random.default_rng(10 * k + seed)
        row_leaf = rng.integers(0, LS, n)
        row_leaf[row_leaf == 5] = 6                 # an empty leaf, selected below
        sel = np.concatenate([[5], rng.permutation(LS)])[:k] if k > 1 \
            else rng.permutation(LS)[:1]
        valid = rng.random(k) < 0.8
        go_left = rng.random(n) < rng.random(LS)[row_leaf]
        left_smaller = rng.random(k) < 0.5          # not the count's: any side

        row_slot = jnp.asarray(row_leaf, jnp.int32)
        in_slot = [(row_slot == int(sel[i])) & bool(valid[i]) for i in range(k)]
        got, counts = (np.asarray(a) for a in _group_smaller_children(
            in_slot, jnp.asarray(go_left), jnp.asarray(left_smaller)))
        assert sorted(got) == list(range(n))

        perm = np.argsort(row_leaf, kind="stable")  # leaves as ranges of perm
        at = 0
        for i in range(k):
            if not valid[i]:
                assert counts[i] == 0
                continue
            rows = perm[row_leaf[perm] == sel[i]]   # the leaf's range
            parted = np.concatenate([rows[go_left[rows]], rows[~go_left[rows]]])
            nl = int(go_left[rows].sum())
            small = parted[:nl] if left_smaller[i] else parted[nl:]
            assert counts[i] == len(small)
            np.testing.assert_array_equal(got[at:at + len(small)], small)
            at += len(small)
        # behind the children, every other row
        assert not np.isin(got[at:], got[:at]).any()


def _walk_eqns(jaxpr, scope=""):
    """Every equation of a jaxpr and of the jaxprs nested in it, with the
    scope names it sits under (outer equations' name stacks included)."""
    for eqn in jaxpr.eqns:
        here = scope + "/" + str(eqn.source_info.name_stack)
        yield eqn, here
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub, here)


def _small_grow_case(n=1000, f=5, categorical=True, **cfg_kw):
    """``(args, cfg)`` of a small call of either grower: ``f`` columns of 32
    bins, the first categorical, eight leaves."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.grower import GrowerConfig
    from lightgbm_tpu.ops.split import SplitParams
    kw = dict(
        num_leaves=8, max_depth=-1, max_bin=32,
        split=SplitParams(0.0, 0.0, 1, 1e-3, 0.0, 0.0, 0.0, 10.0, 10.0, 4),
        feature_fraction_bynode=1.0, hist_method="scatter",
        hist_chunk_rows=8192, sorted_cat=False, frontier_k=3)
    kw.update(cfg_kw)
    rng = np.random.default_rng(0)
    args = (jnp.asarray(rng.integers(0, 32, (n, f)), jnp.uint8),
            jnp.asarray(rng.normal(size=n), jnp.float32),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
            jnp.ones(f, bool), jnp.full(f, 32, jnp.int32),
            jnp.zeros(f, jnp.int32), jnp.full(f, -1, jnp.int32),
            jnp.zeros(f, bool).at[0].set(categorical), jnp.zeros(f, jnp.int32),
            jax.random.PRNGKey(0))
    return args, GrowerConfig(**kw)


def test_partition_sorts_once_and_moves_no_row():
    """Under the ``partition`` scope nothing is gathered into an [n]-sized
    result and nothing is scattered to [n] places; the one data movement is
    one sort; the round loop carries one [n]-sized array, the rows' slots."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.frontier import grow_tree_frontier
    n, f = 1000, 5
    args, cfg = _small_grow_case(n, f)
    jaxpr = jax.make_jaxpr(
        lambda *a: grow_tree_frontier(*a, cfg, with_stats=True))(*args)

    loops = [eqn for eqn, _ in _walk_eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "while" and any(
                 "partition" in s for _, s in _walk_eqns(
                     eqn.params["body_jaxpr"].jaxpr))]
    gathers, scatters = _per_row_ops(jaxpr.jaxpr, n, "partition")
    assert gathers == [] and scatters == []
    sorts = [eqn for eqn, scope in _walk_eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "sort" and "partition" in scope]
    assert [[v.aval.shape for v in e.invars] for e in sorts] == [[(n,), (n,)]]
    assert len(loops) == 1
    n_carried = len(loops[0].params["body_jaxpr"].out_avals)
    carried = [v.aval for v in loops[0].invars[-n_carried:]]
    assert [a.dtype for a in carried if a.shape == (n,)] == [jnp.int32]


def _per_row_ops(jaxpr, n, under):
    """Under the scope ``under``: the operands' shapes of the gathers with an
    [n]-sized result, and the results' shapes of the scatters to [n] places."""
    gathers, scatters = [], []
    for eqn, scope in _walk_eqns(jaxpr):
        name = eqn.primitive.name
        if under not in scope:
            continue
        if name == "gather" and eqn.outvars[0].aval.shape[:1] == (n,):
            gathers.append(eqn.invars[0].aval.shape)
        elif name.startswith("scatter") and eqn.invars[1].aval.shape[:1] == (n,):
            scatters.append(eqn.outvars[0].aval.shape)
    return gathers, scatters


def test_finalize_moves_no_row():
    """Under ``lgbm/finalize`` a row's leaf comes from its slot by selects:
    no gather has an [n]-sized result and nothing is scattered to [n] places."""
    import jax
    from lightgbm_tpu.ops.frontier import grow_tree_frontier
    n = 1000
    args, cfg = _small_grow_case(n)
    jaxpr = jax.make_jaxpr(lambda *a: grow_tree_frontier(*a, cfg))(*args)
    gathers, scatters = _per_row_ops(jaxpr.jaxpr, n, "lgbm/finalize")
    assert gathers == []
    assert scatters == []


@pytest.mark.parametrize("categorical", [False, True], ids=["plain", "categorical"])
def test_leaf_of_slots_when_splits_are_dropped(categorical, monkeypatch):
    """Sixteen leaves a round under a budget of eight: the rounds apply more
    splits than the tree keeps, and the rows of a dropped split's children
    stay in the leaf it split.  The rows' leaves, from their last slots, are
    the leaves the tree's own traversal finds."""
    import jax
    from lightgbm_tpu.ops.frontier import grow_tree_frontier
    from lightgbm_tpu.ops.predict import predict_leaf_binned
    args, cfg = _small_grow_case(3000, categorical=categorical, num_leaves=8,
                                 frontier_k=16, sorted_cat=categorical)
    if categorical:     # gradients that follow the first column's categories
        col = np.asarray(args[0][:, 0]).astype(int)
        args = (args[0], args[1] + 3.0 * (col * 7 % 5 < 2), *args[2:])
    applied = []
    while_loop = jax.lax.while_loop

    def spy(cond, body, init):          # the round loop's final state, eagerly
        out = while_loop(cond, body, init)
        if isinstance(out, dict) and "n_applied" in out:
            applied.append(int(out["n_applied"]))
        return out

    monkeypatch.setattr(jax.lax, "while_loop", spy)
    tree, node_assign = grow_tree_frontier(*args, cfg)
    assert len(applied) == 1 and applied[0] > cfg.num_leaves - 1
    assert int(tree.num_leaves) == 8
    assert bool(np.asarray(tree.is_cat_split).any()) == categorical
    want = predict_leaf_binned(tree, args[0], args[7])
    np.testing.assert_array_equal(np.asarray(node_assign), np.asarray(want))


def test_valid_traverse_looks_nothing_up_per_row():
    """The program that scores a validation set (``GBDT._valid_update_jit``):
    under ``lgbm/valid_traverse`` the traversal gathers nothing per row; the
    one [n]-sized gather left is the score update's ``delta[leaf]``."""
    import jax
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1200, 5))
    X[:, 0] = rng.integers(0, 40, 1200)
    y = (X[:, 1] + (X[:, 0] % 3 == 0) > 0.5).astype(float)
    ds = lgb.Dataset(X[:800], label=y[:800], categorical_feature=[0])
    bst = lgb.train({"objective": "binary", "num_leaves": 8, "verbose": -1},
                    ds, 1, valid_sets=[ds.create_valid(X[800:], label=y[800:])],
                    verbose_eval=False)
    g = bst._gbdt
    tree = g._device_trees[0]
    bins = g.valid_sets[0].device_data().bins
    n, n_leaves = bins.shape[0], tree.leaf_value.shape[0]
    jaxpr = jax.make_jaxpr(g._valid_update_jit, static_argnums=4)(
        g._valid_scores[0], tree, tree.leaf_value, bins, 0)
    gathers, scatters = _per_row_ops(jaxpr.jaxpr, n, "lgbm/valid_traverse")
    assert gathers == [(n_leaves,)]
    assert scatters == []
    whiles = [e for e, s in _walk_eqns(jaxpr.jaxpr)
              if e.primitive.name == "while" and "lgbm/valid_traverse" in s]
    assert len(whiles) == 1             # one loop, over the nodes


@pytest.mark.parametrize("categorical", [False, True], ids=["plain", "categorical"])
@pytest.mark.parametrize("grower", ["frontier", "serial"])
def test_node_assign_is_the_trees_own_traversal(grower, categorical):
    """The rows' leaves as the grower returns them (the frontier grower from
    the rows' last slots, the serial one from the ranges of its partition) are
    the leaves the binned traversal finds for the same rows."""
    import jax
    from lightgbm_tpu.ops.frontier import grow_tree_frontier
    from lightgbm_tpu.ops.grower import grow_tree
    from lightgbm_tpu.ops.predict import predict_leaf_binned
    # 6,000 rows over a first rung of 1,024: the serial grower partitions
    args, cfg = _small_grow_case(6000, categorical=categorical, num_leaves=31,
                                 hist_compact_min_cap=1024, sorted_cat=categorical)
    if categorical:     # gradients that follow the first column's categories
        col = np.asarray(args[0][:, 0]).astype(int)
        args = (args[0], args[1] + 3.0 * (col * 7 % 5 < 2), *args[2:])
    grow = grow_tree_frontier if grower == "frontier" else grow_tree
    tree, node_assign = jax.jit(lambda *a: grow(*a, cfg))(*args)
    assert int(tree.num_leaves) == 31
    assert bool(np.asarray(tree.is_cat_split).any()) == categorical
    want = predict_leaf_binned(tree, args[0], args[7])
    np.testing.assert_array_equal(np.asarray(node_assign), np.asarray(want))


# ---- sums that stay right in a 20-row leaf under a root of 4e5 -------------
def _pocket_rows(seed=28, n=120_000, n_heavy=20_000):
    """120,000 rows over six 16-level columns at LightGBM's default leaf
    limits.  The clicks are the rows of 40 of the 4,096 cells of the first
    three columns, about 29 rows a cell, so trees of 255 leaves end in leaves
    of 20 to 30 rows.  Sample weights: 20,000 rows weigh 80 (hessian 20 each
    at the start: the root's hessian sum is 4e5) and come in pairs of one
    feature vector, one a click and one not, so their gradients cancel in
    every leaf and they steer no split; the others weigh 0.1, so a leaf of 20
    of them sums to a hessian of 0.5."""
    rng = np.random.default_rng(seed)
    X = rng.integers(0, 16, (n, 6)).astype(np.float32)
    cells = rng.choice(16 ** 3, 40, replace=False)
    y = np.isin(X[:, 0] * 256 + X[:, 1] * 16 + X[:, 2], cells).astype(np.float32)
    w = np.full(n, 0.1, np.float32)
    heavy = rng.choice(n, n_heavy, replace=False)
    X[heavy[1::2]] = X[heavy[0::2]]
    w[heavy], y[heavy[0::2]], y[heavy[1::2]] = 80.0, 0.0, 1.0
    return X, y, w


def _follow_in_float64(X, y, w, trees, learning_rate=0.1):
    """What a correct booster writes into the dumped ``trees``
    (``benchmarks.reference.parse_tree``), in float64 numpy, by the method of
    ``benchmarks/reference.py`` with sample weights: route the raw rows down
    each tree, sum each leaf's weighted gradient and hessian from the
    reference's own running score, and follow its own values into the next
    tree.  Per tree: each leaf's rows, value and hessian sum."""
    X, y, w = (np.asarray(a, np.float64) for a in (X, y, w))
    rows = np.arange(len(y))
    pavg = np.sum(w * y) / np.sum(w)
    init = np.log(pavg / (1.0 - pavg))
    score = np.full(len(y), init)
    out = []
    for k, t in enumerate(trees):
        node = np.zeros(len(y), np.int64)
        for _ in range(t["depth"]):
            at = np.maximum(node, 0)
            nxt = np.where(X[rows, t["feature"][at]] <= t["threshold"][at],
                           t["left"][at], t["right"][at])
            node = np.where(node >= 0, nxt, node)
        leaf, nl = ~node, t["num_leaves"]
        p = 1.0 / (1.0 + np.exp(-score))
        g, h, c = (np.bincount(leaf, weights=v, minlength=nl)
                   for v in ((p - y) * w, p * (1.0 - p) * w, np.ones(len(y))))
        value = -g / h * learning_rate
        out.append({"count": c, "hess": h,
                    "value": value + (init if k == 0 else 0.0)})
        score = score + value[leaf]
    return out


@pytest.fixture(scope="module")
def pocket_rows():
    return _pocket_rows()


@pytest.mark.parametrize("grower,extra,rounds", [
    ("frontier", {"frontier_k": 16}, 5),
    ("frontier", {"frontier_k": 1}, 5),
    ("serial", {}, 5),
    # the streamed grower pays a device round trip a split: two trees
    ("serial", {"stream_rows": 32768}, 2)],
    ids=["frontier_k16", "frontier_k1", "serial", "streamed"])
def test_small_leaf_sums_under_a_heavy_root(pocket_rows, grower, extra, rounds):
    """Through ``lgb.train`` at ``min_data_in_leaf=20,
    min_sum_hessian_in_leaf=1e-3, num_leaves=255``: every leaf's value
    against the float64 reference that routes the raw rows down the dumped
    trees.  Before PR 28 the root's sums' float32 error (an ulp of 4e5 is
    0.03) went whole into the small leaves: a row-weighted gap of 1e-2 to 1,
    single leaves 20 times off."""
    from benchmarks.reference import parse_tree
    X, y, w = pocket_rows
    p = {"objective": "binary", "num_leaves": 255, "min_data_in_leaf": 20,
         "min_sum_hessian_in_leaf": 1e-3, "verbose": -1,
         "tree_grower": grower, **extra}
    bst = lgb.train(p, lgb.Dataset(X, label=y, weight=w, params=p), rounds)
    trees = [parse_tree(t) for t in bst.dump_model()["tree_info"]]
    ref = _follow_in_float64(X, y, w, trees)
    assert ref[0]["hess"].sum() > 3e5
    small = 0
    for t, r in zip(trees, ref):
        nl = t["num_leaves"]
        assert nl == 255
        got, want = t["leaf_value"][:nl], r["value"]
        np.testing.assert_array_equal(t["leaf_count"][:nl], r["count"])
        l2 = np.sqrt(np.sum(r["count"] * (got - want) ** 2)
                     / np.sum(r["count"] * want ** 2))
        worst = np.max(np.abs(got - want)
                       / np.maximum(np.abs(want), np.median(np.abs(want))))
        assert l2 < 1e-3 and worst < 1e-2, (l2, worst)
        small += int(np.sum((r["hess"] < 1.0) & (r["count"] < 40)))
    assert small >= 20 * len(trees)     # the regime: small leaves, hessian under 1

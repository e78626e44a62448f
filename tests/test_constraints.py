"""Interaction constraints + CEGB (shape of reference
test_engine.py interaction/cegb tests)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _branch_feature_sets(bst):
    """For every tree: list of (path feature set, leaf) pairs."""
    model = bst.dump_model()
    out = []

    def walk(node, path):
        if "split_index" in node:
            p2 = path | {node["split_feature"]}
            walk(node["left_child"], p2)
            walk(node["right_child"], p2)
        else:
            out.append(path)
    for ti in model["tree_info"]:
        if "split_index" in ti["tree_structure"]:
            walk(ti["tree_structure"], set())
    return out


def test_interaction_constraints(regression_data):
    X, y, _, _ = regression_data
    num_features = X.shape[1]
    groups = [[0, 1, 2], [3, 4, 5, 6, 7]]
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "num_leaves": 15, "verbose": -1,
                     "interaction_constraints": groups}, ds, num_boost_round=10)
    # every root->leaf path must be fully contained in one constraint group
    for path in _branch_feature_sets(bst):
        assert (path <= set(groups[0])) or (path <= set(groups[1])), path
    # training still learns something
    pred = bst.predict(X)
    assert np.mean((pred - y) ** 2) < np.var(y)


def test_interaction_constraints_string_form():
    cfg = lgb.Config.from_params({"interaction_constraints": "[0,1,2],[2,3]"})
    assert cfg.interaction_constraints == [[0, 1, 2], [2, 3]]


def test_interaction_constraints_singleton(regression_data):
    X, y, _, _ = regression_data
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
                     "interaction_constraints": [[0]]}, ds, num_boost_round=5)
    for path in _branch_feature_sets(bst):
        assert path <= {0}


def test_cegb_penalty_split_reduces_leaves(regression_data):
    X, y, _, _ = regression_data
    ds = lgb.Dataset(X, label=y)
    base = lgb.train({"objective": "regression", "num_leaves": 31, "verbose": -1},
                     ds, num_boost_round=5)
    pen = lgb.train({"objective": "regression", "num_leaves": 31, "verbose": -1,
                     "cegb_penalty_split": 1.0}, ds, num_boost_round=5)
    n_base = sum(t["num_leaves"] for t in base.dump_model()["tree_info"])
    n_pen = sum(t["num_leaves"] for t in pen.dump_model()["tree_info"])
    assert n_pen < n_base


def test_cegb_coupled_concentrates_features(regression_data):
    X, y, _, _ = regression_data
    f = X.shape[1]
    ds = lgb.Dataset(X, label=y)
    base = lgb.train({"objective": "regression", "num_leaves": 15, "verbose": -1},
                     ds, num_boost_round=10)
    pen = lgb.train({"objective": "regression", "num_leaves": 15, "verbose": -1,
                     "cegb_penalty_feature_coupled": [5.0] * f},
                    ds, num_boost_round=10)
    used_base = int(np.count_nonzero(base.feature_importance("split")))
    used_pen = int(np.count_nonzero(pen.feature_importance("split")))
    assert used_pen <= used_base


def test_cegb_lazy_trains(regression_data):
    X, y, _, _ = regression_data
    f = X.shape[1]
    ds = lgb.Dataset(X, label=y)
    pen = lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
                     "cegb_penalty_feature_lazy": [0.01] * f},
                    ds, num_boost_round=5)
    pred = pen.predict(X)
    assert np.mean((pred - y) ** 2) < np.var(y)


def test_cegb_scores_differ(regression_data):
    """CEGB penalties must actually change the trained model."""
    X, y, _, _ = regression_data
    f = X.shape[1]
    ds = lgb.Dataset(X, label=y)
    base = lgb.train({"objective": "regression", "num_leaves": 15, "verbose": -1},
                     ds, num_boost_round=5)
    for extra in ({"cegb_penalty_split": 0.5},
                  {"cegb_penalty_feature_coupled": [300.0] * f},
                  {"cegb_penalty_feature_lazy": [0.5] * f}):
        pen = lgb.train({"objective": "regression", "num_leaves": 15,
                         "verbose": -1, **extra}, ds, num_boost_round=5)
        assert not np.allclose(pen.predict(X), base.predict(X)), extra


# ---------------------------------------------------------------------------
# monotone constraints — intermediate mode (IntermediateLeafConstraints,
# reference monotone_constraints.hpp:514; vectorized rectangle propagation)
def _monotone_violation(bst, X, fidx, sign, grid_lo=-2, grid_hi=2):
    """Max violation of sign-monotonicity in feature ``fidx`` over a sweep."""
    base = X[:200].copy()
    prev, worst = None, 0.0
    for v in np.linspace(grid_lo, grid_hi, 50):
        b = base.copy()
        b[:, fidx] = v
        p = bst.predict(b)
        if prev is not None:
            worst = max(worst, float(np.max(sign * (prev - p))))
        prev = p
    return worst


def _monotone_fixture(seed=0, n=4000):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-2, 2, (n, 4))
    y = (1.5 * X[:, 0] + np.sin(2 * X[:, 1]) + 0.3 * X[:, 2] ** 2
         - 0.8 * X[:, 3] + rng.normal(0, 0.2, n))
    return X, y


def _train_monotone(X, y, method, cons=(1, 0, 0, -1), rounds=25):
    ds = lgb.Dataset(X, label=y)
    return lgb.train({"objective": "regression", "num_leaves": 63,
                      "verbose": -1, "monotone_constraints": list(cons),
                      "monotone_constraints_method": method,
                      "min_data_in_leaf": 20}, ds, rounds)


def test_monotone_intermediate_preserves_monotonicity():
    X, y = _monotone_fixture()
    bst = _train_monotone(X, y, "intermediate")
    assert _monotone_violation(bst, X, 0, +1) <= 1e-10
    assert _monotone_violation(bst, X, 3, -1) <= 1e-10


def test_monotone_intermediate_less_constraining_than_basic():
    """Intermediate bounds children by actual sibling outputs instead of the
    midpoint, so it finds splits basic rejects -> strictly better fit here."""
    X, y = _monotone_fixture()
    basic = _train_monotone(X, y, "basic")
    inter = _train_monotone(X, y, "intermediate")
    l2_basic = float(np.mean((basic.predict(X) - y) ** 2))
    l2_inter = float(np.mean((inter.predict(X) - y) ** 2))
    assert l2_inter < l2_basic
    assert not np.allclose(basic.predict(X[:100]), inter.predict(X[:100]))


@pytest.mark.parametrize("seed", [1, 3])
def test_monotone_advanced_holds_and_differs(seed):
    """Advanced re-derives child bounds from rect comparability: it must
    stay monotone in both constrained features, fit at least as well as
    intermediate on interaction data (looser-but-valid bounds admit more
    splits), and actually be a distinct mode (reference
    AdvancedLeafConstraints, monotone_constraints.hpp:230-375)."""
    X, y = _monotone_fixture(seed=seed)
    adv = _train_monotone(X, y, "advanced")
    assert _monotone_violation(adv, X, 0, +1) <= 1e-10
    assert _monotone_violation(adv, X, 3, -1) <= 1e-10
    inter = _train_monotone(X, y, "intermediate")
    l2_adv = float(np.mean((adv.predict(X) - y) ** 2))
    l2_inter = float(np.mean((inter.predict(X) - y) ** 2))
    # comparable fit (greedy growth under different-but-valid bounds can
    # land either way on a given seed; on this fixture advanced wins)
    assert l2_adv <= l2_inter * 1.05, (l2_adv, l2_inter)
    assert adv.model_to_string() != inter.model_to_string()


def test_monotone_advanced_both_signs():
    rng = np.random.default_rng(9)
    n = 3000
    X = rng.uniform(-2, 2, size=(n, 4))
    y = (2.0 * X[:, 0] - 1.5 * X[:, 1] + np.sin(2 * X[:, 2]) * (X[:, 3] > 0)
         + 0.1 * rng.normal(size=n))
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "verbose": -1, "monotone_constraints": [1, -1, 0, 0],
                     "monotone_constraints_method": "advanced"}, ds, 15)
    assert _monotone_violation(bst, X, 0, +1) <= 1e-10
    assert _monotone_violation(bst, X, 1, -1) <= 1e-10


def test_monotone_intermediate_multiclass_and_depth():
    X, y = _monotone_fixture(seed=2)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "max_depth": 4, "verbose": -1,
                     "monotone_constraints": [1, 0, 0, 0],
                     "monotone_constraints_method": "intermediate"}, ds, 10)
    assert _monotone_violation(bst, X, 0, +1) <= 1e-10

"""The split a grower reports is the best one.

The benchmark holds a reported gain against the reference's sums and cannot
see whether another split would have been better (``PERF.md`` section 7).
Here, on the CPU: one root split of each grower against a brute force over
every candidate in float64, for a numerical column, a column with missing
values and a categorical column.  Every column has few distinct values, so
each value is a bin of its own and the candidates can be listed from the
raw values without the program's bin mappers."""
import numpy as np
import pytest

import lightgbm_tpu as lgb

GROWERS = {"frontier": {"tree_grower": "frontier"},
           "serial": {"tree_grower": "serial"},
           "stream": {"stream_rows": 1024}}
MIN_DATA, CAT_L2 = 20, 10.0


def _data(kind, n=3000, seed=7):
    rng = np.random.default_rng(seed)
    X = np.column_stack([rng.integers(0, 12, n), rng.integers(0, 8, n),
                         rng.integers(0, 3, n)]).astype(np.float64)
    y = 0.15 * X[:, 0] + rng.normal(scale=0.5, size=n)
    if kind == "numerical":
        y += 2.0 * (X[:, 1] > 4)
    elif kind == "missing":
        y += 1.0 * (X[:, 1] > 4)
        gone = rng.random(n) < 0.2
        X[gone, 1] = np.nan
        y[gone] += 3.0                  # the missing rows belong with the high side
    else:
        y += 2.5 * (X[:, 2] == 1)
    return X, y


def _gain(g, h, l2=0.0):
    return g * g / (h + l2)


def _brute_force(X, y, categorical):
    """(gain, column, rows on the left) of the best candidate.  ``g = -y``,
    ``h = 1`` (L2 loss from a zero score); the gain is the children's less
    the parent's, a categorical candidate is one category against the rest
    with ``cat_l2`` on both children, a missing value goes to either side."""
    g, n = -y, len(y)
    parent = _gain(g.sum(), float(n))
    best = (-np.inf, None, None)
    for j in range(X.shape[1]):
        col = X[:, j]
        nan = np.isnan(col)
        values = np.unique(col[~nan])
        if j in categorical:
            sides = [col == v for v in values]
            l2 = CAT_L2
        else:
            # the program's candidates (``split._split_gain_matrix``'s
            # ``valid_t``): with a missing bin the last present value is no
            # threshold, so "the missing rows alone on one side" is never
            # offered, though it is the better split of this column
            # (PERF.md section 7, fault 6); ``values[:-1]`` follows it
            below = [(col <= v) & ~nan for v in values[:-1]]
            sides = below + ([s | nan for s in below] if nan.any() else [])
            l2 = 0.0
        for left in sides:
            nl = int(left.sum())
            if nl < MIN_DATA or n - nl < MIN_DATA:
                continue
            gain = (_gain(g[left].sum(), float(nl), l2)
                    + _gain(g[~left].sum(), float(n - nl), l2) - parent)
            if gain > best[0]:
                best = (gain, j, nl)
    return best


@pytest.mark.parametrize("kind", ["numerical", "missing", "categorical"])
@pytest.mark.parametrize("grower", sorted(GROWERS))
def test_root_split_is_the_best_by_brute_force(grower, kind):
    X, y = _data(kind)
    categorical = [2] if kind == "categorical" else []
    want_gain, want_col, want_left = _brute_force(X, y, categorical)
    assert want_col == {"numerical": 1, "missing": 1, "categorical": 2}[kind]

    params = {"objective": "regression", "num_leaves": 2, "verbose": -1,
              "boost_from_average": False, "min_data_in_leaf": MIN_DATA,
              "min_sum_hessian_in_leaf": 1e-3, "cat_l2": CAT_L2,
              "min_data_per_group": 1, **GROWERS[grower]}
    ds = lgb.Dataset(X, label=y, params=params,
                     categorical_feature=categorical)
    bst = lgb.train(params, ds, num_boost_round=1)
    root = bst.dump_model()["tree_info"][0]["tree_structure"]
    assert root["split_feature"] == want_col
    assert root["left_child"]["leaf_count"] == want_left
    assert root["right_child"]["leaf_count"] == len(y) - want_left
    assert root["split_gain"] == pytest.approx(want_gain, rel=1e-4)

"""Unit tests for the compute ops: histogram kernels and split search."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.medium

from lightgbm_tpu.ops.histogram import build_histogram, fold_hist
from lightgbm_tpu.ops.split import SplitParams, find_best_split, leaf_output


def _ref_histogram(bins, grad, hess, mask, max_bin):
    n, f = bins.shape
    out = np.zeros((f, max_bin, 3))
    for i in range(n):
        if mask[i] == 0:
            continue
        for j in range(f):
            b = bins[i, j]
            out[j, b, 0] += grad[i] * mask[i]
            out[j, b, 1] += hess[i] * mask[i]
            out[j, b, 2] += mask[i]
    return out


@pytest.mark.parametrize("method", ["onehot", "scatter"])
def test_histogram_matches_reference(method):
    rng = np.random.default_rng(0)
    n, f, b = 500, 4, 16
    bins = rng.integers(0, b, size=(n, f)).astype(np.uint8)
    grad = rng.normal(size=n).astype(np.float32)
    hess = rng.uniform(0.1, 1.0, size=n).astype(np.float32)
    mask = (rng.uniform(size=n) < 0.7).astype(np.float32)
    got = np.asarray(fold_hist(build_histogram(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(mask), b, method=method, chunk_rows=128)))
    want = _ref_histogram(bins, grad, hess, mask, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def _default_params(**kw):
    d = dict(lambda_l1=0.0, lambda_l2=0.0, min_data_in_leaf=1,
             min_sum_hessian_in_leaf=0.0, min_gain_to_split=0.0,
             max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
             cat_l2=10.0, max_cat_to_onehot=4)
    d.update(kw)
    return SplitParams(**d)


def _split_inputs(hist, num_bins):
    f = hist.shape[0]
    return dict(
        hist=jnp.asarray(hist, jnp.float32),
        num_bins=jnp.asarray(num_bins, jnp.int32),
        default_bins=jnp.zeros(f, jnp.int32),
        nan_bins=jnp.full(f, -1, jnp.int32),
        is_categorical=jnp.zeros(f, bool),
        monotone=jnp.zeros(f, jnp.int8),
        feature_mask=jnp.ones(f, jnp.float32),
    )


def test_split_finds_obvious_boundary():
    # feature 0: bins 0-3, gradient +1 for bins 0,1 and -1 for bins 2,3
    b = 8
    hist = np.zeros((2, b, 3))
    for bin_id, g in [(0, 10.0), (1, 10.0), (2, -10.0), (3, -10.0)]:
        hist[0, bin_id] = [g, 10.0, 10.0]
    # feature 1: no signal
    hist[1, 0] = [0.0, 40.0, 40.0]
    inp = _split_inputs(hist, [4, 1])
    p = _default_params()
    s = find_best_split(**inp, sum_g=0.0, sum_h=40.0, count=40.0, p=p)
    assert int(s.feature) == 0
    assert int(s.threshold) == 1          # bins <= 1 go left
    assert float(s.gain) > 0
    assert float(s.left_sum_g) == pytest.approx(20.0)
    assert float(s.left_output) == pytest.approx(-1.0)   # -G/H
    assert float(s.right_output) == pytest.approx(1.0)


def test_split_min_data_gate():
    b = 4
    hist = np.zeros((1, b, 3))
    hist[0, 0] = [5.0, 2.0, 2.0]
    hist[0, 1] = [-5.0, 38.0, 38.0]
    inp = _split_inputs(hist, [2])
    s = find_best_split(**inp, sum_g=0.0, sum_h=40.0, count=40.0,
                        p=_default_params(min_data_in_leaf=5))
    assert float(s.gain) < 0  # blocked: left side has only 2 rows


def test_split_l2_shrinks_gain():
    b = 4
    hist = np.zeros((1, b, 3))
    hist[0, 0] = [10.0, 10.0, 10.0]
    hist[0, 1] = [-10.0, 10.0, 10.0]
    inp = _split_inputs(hist, [2])
    s0 = find_best_split(**inp, sum_g=0.0, sum_h=20.0, count=20.0, p=_default_params())
    s1 = find_best_split(**inp, sum_g=0.0, sum_h=20.0, count=20.0,
                         p=_default_params(lambda_l2=10.0))
    assert float(s1.gain) < float(s0.gain)


def test_split_missing_direction():
    # NaN bin (last) holds strongly-negative-gradient rows: best with
    # missing going right toward the negative side
    b = 8
    f = 1
    hist = np.zeros((f, b, 3))
    hist[0, 0] = [10.0, 10.0, 10.0]
    hist[0, 1] = [-2.0, 10.0, 10.0]
    hist[0, 3] = [-8.0, 5.0, 5.0]     # NaN bin (num_bin=4 -> nan bin idx 3)
    inp = _split_inputs(hist, [4])
    inp["nan_bins"] = jnp.asarray([3], jnp.int32)
    s = find_best_split(**inp, sum_g=0.0, sum_h=25.0, count=25.0, p=_default_params())
    assert float(s.gain) > 0
    assert not bool(s.default_left)   # missing joins the negative (right) side


def test_monotone_rejects_violation():
    b = 4
    hist = np.zeros((1, b, 3))
    # increasing feature -> decreasing output (violates +1 monotone)
    hist[0, 0] = [-10.0, 10.0, 10.0]   # left output +1
    hist[0, 1] = [10.0, 10.0, 10.0]    # right output -1
    inp = _split_inputs(hist, [2])
    inp["monotone"] = jnp.asarray([1], jnp.int8)
    s = find_best_split(**inp, sum_g=0.0, sum_h=20.0, count=20.0, p=_default_params())
    assert float(s.gain) < 0
    inp["monotone"] = jnp.asarray([-1], jnp.int8)
    s = find_best_split(**inp, sum_g=0.0, sum_h=20.0, count=20.0, p=_default_params())
    assert float(s.gain) > 0


def test_leaf_output_l1():
    p = _default_params(lambda_l1=5.0)
    assert float(leaf_output(10.0, 10.0, p)) == pytest.approx(-0.5)
    assert float(leaf_output(3.0, 10.0, p)) == pytest.approx(0.0)


def test_gather_rows_compaction():
    from lightgbm_tpu.ops.histogram import build_histogram, gather_rows
    rng = np.random.default_rng(3)
    n, f, b = 1000, 5, 16
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=n) < 0.3).astype(np.float32)) * 1.5
    cap = int(jnp.sum(mask > 0)) + 7
    bc, gc, hc, mc = gather_rows(bins, g, h, mask, cap)
    assert bc.shape == (cap, f)
    # same histogram from the compacted buffer as from the full masked pass
    full = fold_hist(build_histogram(bins, g, h, mask, b, method="scatter"))
    comp = fold_hist(build_histogram(bc, gc, hc, mc, b, method="scatter"))
    np.testing.assert_allclose(np.asarray(full), np.asarray(comp), atol=1e-4)


def test_hist_onehot_matches_scatter():
    from lightgbm_tpu.ops.histogram import build_histogram, fold_hist
    rng = np.random.default_rng(4)
    n, f, b = 3000, 7, 32
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=n).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=n) < 0.7).astype(np.float32))
    a = fold_hist(build_histogram(bins, g, h, mask, b, method="scatter"))
    c = fold_hist(build_histogram(bins, g, h, mask, b, method="onehot",
                                  chunk_rows=1024))
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-3)


def test_grower_compaction_parity():
    """Trees grown with and without adaptive compaction are identical."""
    from lightgbm_tpu.ops.grower import GrowerConfig, grow_tree
    from lightgbm_tpu.ops.split import SplitParams
    rng = np.random.default_rng(5)
    n, f, b = 4000, 6, 16
    bins = jnp.asarray(rng.integers(0, b, size=(n, f), dtype=np.uint8))
    g = jnp.asarray(rng.normal(size=n).astype(np.float32))
    h = jnp.asarray(np.ones(n, np.float32))
    meta = dict(
        num_bins=jnp.full(f, b, jnp.int32),
        default_bins=jnp.zeros(f, jnp.int32),
        nan_bins=jnp.full(f, -1, jnp.int32),
        is_categorical=jnp.zeros(f, bool),
        monotone=jnp.zeros(f, jnp.int8))
    sp = SplitParams(lambda_l1=0.0, lambda_l2=1.0, min_data_in_leaf=20,
                     min_sum_hessian_in_leaf=1e-3, min_gain_to_split=0.0,
                     max_delta_step=0.0, path_smooth=0.0, cat_smooth=10.0,
                     cat_l2=10.0, max_cat_to_onehot=4)
    base = dict(num_leaves=31, max_depth=-1, max_bin=b, split=sp,
                feature_fraction_bynode=1.0, hist_method="scatter",
                hist_chunk_rows=8192)
    key = jax.random.PRNGKey(0)
    rw = jnp.ones(n, jnp.float32)
    fm = jnp.ones(f, jnp.float32)
    t1, na1 = grow_tree(bins, g, h, rw, fm, **meta, key=key,
                        cfg=GrowerConfig(**base, hist_compact=False))
    t2, na2 = grow_tree(bins, g, h, rw, fm, **meta, key=key,
                        cfg=GrowerConfig(**base, hist_compact=True,
                                         hist_compact_min_cap=256))
    assert int(t1.num_leaves) == int(t2.num_leaves)
    np.testing.assert_array_equal(np.asarray(na1), np.asarray(na2))
    np.testing.assert_allclose(np.asarray(t1.leaf_value),
                               np.asarray(t2.leaf_value), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                  np.asarray(t2.split_feature))


def test_node_feature_mask_sizes_from_allowed_subset():
    """feature_fraction_bynode composes with feature_fraction: the per-node
    kept count is round(frac * allowed), where allowed is the BYTREE-
    selected feature count — not the total width (sizing from the total
    made bynode a silent no-op whenever bytree already thinned the mask,
    the round-5 advisor bug)."""
    from lightgbm_tpu.ops.grower import node_feature_mask_for
    key = jax.random.PRNGKey(42)
    f_full, n_allowed = 20, 10
    bytree = jnp.zeros(f_full, jnp.float32).at[:n_allowed].set(1.0)
    for step in range(5):
        kept = node_feature_mask_for(key, step, bytree, 0.5)
        kept_n = int(jnp.sum(kept > 0))
        assert kept_n == 5, f"step {step}: kept {kept_n}, want 5"
        # never resurrects a bytree-dropped feature
        assert int(jnp.sum(kept[n_allowed:] > 0)) == 0
    # full-width mask keeps the historical round(frac * F) behavior
    full = jnp.ones(f_full, jnp.float32)
    assert int(jnp.sum(node_feature_mask_for(key, 0, full, 0.5) > 0)) == 10
    # floor of one feature even at tiny fractions
    assert int(jnp.sum(node_feature_mask_for(key, 0, bytree, 0.01) > 0)) == 1
    # works under jit (n_take must stay traceable)
    jitted = jax.jit(lambda k, m: node_feature_mask_for(k, 3, m, 0.5))
    assert int(jnp.sum(jitted(key, bytree) > 0)) == 5


def _small_child_case(case):
    """A seeded [3, 64, 3] histogram whose totals are of the order 4e5 in
    hessian and 1e5 in rows and whose best split parts off a child of 20 or
    25 light rows, hessian 0.514 or 0.6425; float64, from rows."""
    rng = np.random.default_rng(28)
    b, n_bulk, n_tail = 64, 100_000, 20
    n_nan = {"missing_right": 5, "missing_left": 1000, "categorical": 0}[case]
    n = n_bulk + n_tail + n_nan
    tail = slice(n_bulk, n_bulk + n_tail)
    h = np.full(n, 4.0) * rng.uniform(0.9, 1.1, n)
    g = rng.normal(0.0, 1e-3, n) * h
    light = np.r_[tail] if case != "missing_right" else np.r_[n_bulk:n]
    h[light], g[light] = 0.0257, -0.15
    bins = rng.integers(0, b, (n, 3))
    if case == "categorical":       # bin 0 the catch-all, 1 the bulk, 2 and 3 the tail
        bins[:n_bulk, 0] = 1
        bins[tail, 0] = rng.integers(2, 4, n_tail)
    else:                           # the tail in the last two bins before the NaN bin
        bins[:n_bulk, 0] = rng.integers(0, b - 3, n_bulk)
        bins[tail, 0] = rng.integers(b - 3, b - 1, n_tail)
        bins[n_bulk + n_tail:, 0] = b - 1
    hist = np.zeros((3, b, 3))
    for f in range(3):
        for c, v in enumerate((g, h, np.ones(n))):
            hist[f, :, c] = np.bincount(bins[:, f], weights=v, minlength=b)
    return hist, light


@pytest.mark.parametrize("case", ["missing_right", "missing_left",
                                  "categorical"])
def test_small_child_sums_are_relative_to_the_child(case):
    """The split step under totals of 4e5: the small right child's three sums
    come from its own bins (within 1e-3 of float64, its count exact), and the
    sides add up to the parent's.  ``right = total - left`` in float32 puts
    an ulp of the total (0.03) on a hessian sum of 0.5."""
    hist64, light = _small_child_case(case)
    hist = hist64.astype(np.float32)
    inp = _split_inputs(hist, [4 if case == "categorical" else 64, 64, 64])
    if case == "categorical":
        inp["is_categorical"] = jnp.asarray([True, False, False])
    else:
        inp["nan_bins"] = jnp.asarray([63, -1, -1], jnp.int32)
    tot = hist.astype(np.float64)[0].sum(axis=0)
    s = find_best_split(**inp, sum_g=np.float32(tot[0]), sum_h=np.float32(tot[1]),
                        count=np.float32(tot[2]),
                        p=_default_params(min_data_in_leaf=20,
                                          min_sum_hessian_in_leaf=1e-3))
    assert int(s.feature) == 0 and float(s.gain) > 0
    assert bool(s.default_left) == (case == "missing_left")
    # what float64 makes of the same float32 bins, on the child's side
    side = {"missing_right": [61, 62, 63], "missing_left": [61, 62],
            "categorical": [2, 3]}[case]
    want = hist.astype(np.float64)[0, side].sum(axis=0)
    assert want[2] == len(light) and want[1] < 1.0
    got = np.array([s.right_sum_g, s.right_sum_h, s.right_count], np.float64)
    assert got[2] == want[2]
    np.testing.assert_allclose(got[:2], want[:2], rtol=1e-3)
    left = np.array([s.left_sum_g, s.left_sum_h, s.left_count], np.float64)
    np.testing.assert_allclose(left + got, tot, rtol=2e-7, atol=2e-7 * tot[1])


# ---- the binned traversal: a scan over the nodes, held to a per-row walk ----
def _random_tree_arrays(rng, n_leaves, n_slots, n_feat, n_bins, cw, cat_feats=()):
    """A tree of ``n_leaves`` leaves in arrays that hold ``n_slots``, numbered
    as every grower numbers it: node ``j`` is the ``j``-th split, its left
    child keeps the split leaf's id and its right child is leaf ``j + 1``."""
    from lightgbm_tpu.ops.grower import TreeArrays
    m = n_slots - 1
    left, right = np.full(m, -1, np.int32), np.full(m, -1, np.int32)
    feat = np.full(m, -1, np.int32)
    thr, dleft, iscat = np.zeros(m, np.int32), np.zeros(m, bool), np.zeros(m, bool)
    bits = np.zeros((m, cw), np.int64)
    points_at = {0: None}               # leaf -> (node, side) whose child it is
    for j in range(n_leaves - 1):
        leaf = int(rng.choice(sorted(points_at)))
        if points_at[leaf] is not None:
            node, side = points_at[leaf]
            (left if side else right)[node] = j
        left[j], right[j] = ~leaf, ~(j + 1)
        points_at[leaf], points_at[j + 1] = (j, True), (j, False)
        feat[j] = rng.integers(0, n_feat)
        thr[j] = rng.integers(n_bins // 4, 3 * n_bins // 4)
        dleft[j] = rng.random() < 0.5
        iscat[j] = feat[j] in cat_feats
        bits[j] = rng.integers(0, 2 ** 32, cw)
    z = lambda k: jnp.zeros(k, jnp.float32)
    return TreeArrays(
        split_feature=jnp.asarray(feat), threshold=jnp.asarray(thr),
        default_left=jnp.asarray(dleft), is_cat_split=jnp.asarray(iscat),
        cat_bits=jnp.asarray(bits.astype(np.uint32).view(np.int32).reshape(m, cw)),
        split_gain=z(m), left_child=jnp.asarray(left), right_child=jnp.asarray(right),
        leaf_value=z(n_slots), leaf_count=z(n_slots), leaf_weight=z(n_slots),
        internal_value=z(m), internal_count=z(m), num_leaves=jnp.int32(n_leaves))


def _walk_rows(tree, bins, nan_bins, efb=None):
    """Each row down the tree on its own, in NumPy."""
    from lightgbm_tpu.io.efb import decode_bundle_column
    t = {k: np.asarray(v) for k, v in tree._asdict().items()}
    out = np.zeros(len(bins), np.int32)
    for i, row in enumerate(np.asarray(bins).astype(np.int64)):
        node = 0 if t["num_leaves"] > 1 else -1
        while node >= 0:
            f = t["split_feature"][node]
            if efb is None:
                b = row[f]
            else:
                b = int(decode_bundle_column(row[efb[0][f]], efb[1][f], efb[2][f]))
            if t["is_cat_split"][node]:
                word = int(t["cat_bits"][node].view(np.uint32)[b >> 5])
                go_left = (word >> (b & 31)) & 1 == 1
            elif nan_bins[f] >= 0 and b == nan_bins[f]:
                go_left = t["default_left"][node]
            else:
                go_left = b <= t["threshold"][node]
            node = t["left_child"][node] if go_left else t["right_child"][node]
        out[i] = ~node
    return out


@pytest.mark.parametrize("case", ["missing_default_left", "missing_default_right",
                                  "categorical_words", "efb_bundles", "uint16_bins",
                                  "no_split", "fewer_nodes_than_slots"])
def test_predict_leaf_binned_matches_a_per_row_walk(case):
    from lightgbm_tpu.ops.predict import predict_leaf_binned
    rng = np.random.default_rng(29)
    n, f, n_bins, dtype, efb = 700, 6, 90, np.uint8, None
    n_leaves = n_slots = 31
    cat_feats = ()
    if case == "categorical_words":
        cat_feats = (1, 4)                      # 90 bins: three bit-set words
    elif case == "uint16_bins":
        n_bins, dtype, cat_feats = 600, np.uint16, (2,)
    elif case == "no_split":
        n_leaves = 1
    elif case == "fewer_nodes_than_slots":
        n_leaves = 9
    cw = (n_bins + 31) // 32
    tree = _random_tree_arrays(rng, n_leaves, n_slots, f, n_bins, cw, cat_feats)
    nan_bins = np.where(np.arange(f) % 2 == 0, n_bins - 1, -1).astype(np.int32)
    if case.startswith("missing_default"):
        tree = tree._replace(default_left=jnp.full(
            n_slots - 1, case == "missing_default_left"))
    if case == "efb_bundles":
        # features 0..5 over three bundle columns, each feature's range
        # [off, off + nb - 1) of its column (io/efb.py)
        efb = (np.array([0, 0, 1, 1, 1, 2]), np.array([1, 40, 1, 30, 60, 1]),
               np.array([40, 50, 30, 31, 30, 90]))
        bins = rng.integers(0, 90, (n, 3)).astype(dtype)
        nan_bins = np.where(np.arange(f) % 2 == 0, efb[2] - 1, -1).astype(np.int32)
    else:
        bins = rng.integers(0, n_bins, (n, f)).astype(dtype)
    got = np.asarray(jax.jit(lambda t, b: predict_leaf_binned(
        t, b, jnp.asarray(nan_bins), efb=efb))(tree, jnp.asarray(bins)))
    want = _walk_rows(tree, bins, nan_bins, efb)
    np.testing.assert_array_equal(got, want)
    assert len(np.unique(want)) >= min(n_leaves, 5)     # the rows do spread over the tree


@pytest.mark.parametrize("grower", ["frontier_k16", "frontier_k1", "serial", "streamed"])
def test_children_are_numbered_after_their_parent(grower):
    """What the traversal's one pass over the nodes rests on: node ``j`` is the
    ``j``-th split, so an internal child's index exceeds its parent's."""
    import lightgbm_tpu as lgb
    params = {"frontier_k16": {"tree_grower": "frontier", "frontier_k": 16},
              "frontier_k1": {"tree_grower": "frontier", "frontier_k": 1},
              "serial": {"tree_grower": "serial"},
              "streamed": {"tree_grower": "serial", "stream_rows": 2048}}[grower]
    rng = np.random.default_rng(3)
    X = rng.normal(size=(6000, 6))
    X[:, 0] = rng.integers(0, 40, 6000)
    y = X[:, 1] * 2 + np.sin(X[:, 2] * 3) + (X[:, 0] % 5 > 2) + 0.1 * rng.normal(size=6000)
    bst = lgb.train(dict(params, objective="regression", num_leaves=31, max_bin=63,
                         min_data_in_leaf=5, verbose=-1),
                    lgb.Dataset(X, label=y, categorical_feature=[0]), 3)
    if grower == "streamed":
        from lightgbm_tpu.stream.booster import StreamGBDT
        assert isinstance(bst._gbdt, StreamGBDT)
    for tree in bst._gbdt.models:
        m = tree.num_leaves - 1
        assert m > 10
        parents = np.arange(m)
        for child in (tree.left_child[:m], tree.right_child[:m]):
            assert np.all((child < 0) | (child > parents))

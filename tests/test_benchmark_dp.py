"""The benchmark's pieces for a table in shards over a mesh, on the CPU: the
row ranges of the generator, the reference that follows the trees shard by
shard, and the per-chip reading of a device trace."""
import json
import os

import numpy as np
import pytest

from benchmarks import (chip_reduce, datagen, datagen_rows, reference,
                        reference_dp, trace_reduce)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spec():
    with open(os.path.join(ROOT, "benchmarks", "configs", "criteo67-dp4.json")) as f:
        return json.load(f)["data"]


@pytest.mark.parametrize("n,shards,stream", [(10_003, 4, 0), (2_001, 3, 1)])
def test_shards_laid_end_to_end_are_the_whole_table(n, shards, stream, monkeypatch):
    """Byte for byte ``datagen.make``'s table, the last block short and
    blocks cut by the shards' bounds included (blocks of 1,024 rows here)."""
    monkeypatch.setattr(datagen, "BLOCK_ROWS", 1024)
    spec, seed = _spec(), 3_700_000_377
    x, y = datagen.make(spec, n, seed, stream=stream)
    bounds = datagen_rows.shard_bounds(n, shards)
    assert bounds[0][0] == 0 and bounds[-1][1] == n
    assert all(b[1] == c[0] for b, c in zip(bounds, bounds[1:]))
    parts = [datagen_rows.make_rows(spec, n, lo, hi, seed, stream) for lo, hi in bounds]
    assert np.concatenate([p[0] for p in parts]).tobytes() == x.tobytes()
    assert np.concatenate([p[1] for p in parts]).tobytes() == y.tobytes()


def _tree(x, leaves, rng):
    """A tree in ``reference.parse_tree``'s form: the leaf with the most rows
    split, again and again, at the median of a random column there, so no
    leaf is empty."""
    x = x.astype(np.float32)
    nodes, rows = [], {0: np.arange(len(x))}
    parent_of = {0: None}                      # leaf id -> (node, side)
    for leaf in range(1, leaves):
        split = max(rows, key=lambda j: len(rows[j]))
        while True:
            col = int(rng.integers(x.shape[1]))
            thr = float(np.median(x[rows[split], col]))
            left = x[rows[split], col] <= thr
            if 0 < left.sum() < len(left):
                break
        i = len(nodes)
        nodes.append({"feature": col, "threshold": thr, "left": ~split, "right": ~leaf})
        if parent_of[split] is not None:
            node, side = parent_of[split]
            nodes[node][side] = i
        parent_of[split], parent_of[leaf] = (i, "left"), (i, "right")
        rows[split], rows[leaf] = rows[split][left], rows[split][~left]
    depth = {0: 1}
    for i, nd in enumerate(nodes):             # a child's id is above its parent's
        for side in ("left", "right"):
            if nd[side] >= 0:
                depth[nd[side]] = depth[i] + 1
    t = {"num_leaves": leaves, "depth": max(depth.values())}
    for key, dtype in (("feature", np.int32), ("threshold", np.float64),
                       ("left", np.int32), ("right", np.int32)):
        t[key] = np.array([nd[key] for nd in nodes], dtype)
    return t


@pytest.fixture(scope="module")
def grown():
    """Four trees of 31 leaves over 8,003 rows, and 2,001 validation rows."""
    spec, rng = _spec(), np.random.default_rng(11)
    x, y = datagen.make(spec, 8_003, 11)
    xv, yv = datagen.make(spec, 2_001, 11, stream=1)
    return x, y, xv, yv, [_tree(x, 31, rng) for _ in range(4)], {"learning_rate": 0.1}


@pytest.mark.parametrize("control", [{}, {"precision": "bfloat16"}])
def test_shards_followed_apart_equal_the_whole_rows(grown, control):
    """Four shards on four devices give ``reference.follow``'s record on the
    whole rows: counts exactly; sums are float32 within a 4,096-row block and
    float64 across, and the shards cut the blocks elsewhere, so values,
    gains and losses agree to a few float32 roundings of a block's sum."""
    import jax
    x, y, xv, yv, trees, params = grown
    whole = reference.follow(reference.Rows(x, y), trees, params,
                             valid=reference.Rows(xv, yv), **control)
    cut = [0, 2_000, 4_100, 6_000, 8_003]
    vcut = [0, 500, 1_000, 1_500, 2_001]
    rows = reference_dp.Shards([x[a:b] for a, b in zip(cut, cut[1:])],
                               [y[a:b] for a, b in zip(cut, cut[1:])],
                               devices=jax.devices()[:4])
    valid = reference_dp.Shards([xv[a:b] for a, b in zip(vcut, vcut[1:])],
                                [yv[a:b] for a, b in zip(vcut, vcut[1:])],
                                devices=jax.devices()[:4])
    assert {c[0].device for c in rows.chunks} == set(jax.devices()[:4])
    apart = reference_dp.follow(rows, trees, params, valid=valid, **control)
    assert apart["init_score"] == whole["init_score"]
    for a, b in zip(apart["trees"], whole["trees"]):
        np.testing.assert_array_equal(a["leaf_count"], b["leaf_count"])
    # the gaps that decide ``correct``, a hundredth of the tightest limit
    # (criteo67's valid_auc_gap, 3e-5) and under: a leaf value near zero is
    # a difference of large sums and differs relative to itself
    gaps = reference.compare(apart, whole)
    assert gaps.pop("count_gap") == 0.0
    assert all(v <= 3e-7 for k, v in gaps.items() if not k.startswith("worst_"))


def test_a_leaf_is_the_one_whose_path_holds(grown):
    """The path matrix gives every row the leaf ``value <= threshold`` walks
    it to."""
    import jax.numpy as jnp
    x, _, _, _, trees, _ = grown
    xt = jnp.asarray(x[:4096].T.astype(np.float32))
    for t in trees:
        m, need = reference_dp.paths(t)
        thr = np.array([reference._f32_at_or_below(v) for v in t["threshold"]], np.float32)
        got = np.asarray(reference_dp._leaf_of(xt, jnp.asarray(t["feature"]),
                                               jnp.asarray(thr), jnp.asarray(m),
                                               jnp.asarray(need)))
        want = np.asarray(reference._leaf_of(jnp.asarray(x[:4096].astype(np.float32)),
                                             jnp.asarray(t["feature"]), jnp.asarray(thr),
                                             jnp.asarray(t["left"]), jnp.asarray(t["right"]),
                                             t["depth"]))
        np.testing.assert_array_equal(got, want)


def test_one_chip_read_per_chip_is_today_s_reading():
    """On the recorded one-chip trace the per-chip reduction gives what the
    accepted reduction gives, digit for digit: busy time, every operation's
    self time, and so every share built on them."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "trace_small.json")) as f:
        trace = json.load(f)["trace"]
    chip = chip_reduce.pacing_chip(trace)
    assert [chip] == list(trace["device"])
    assert chip_reduce.busy_by_chip(trace)[chip] == trace_reduce.busy_seconds(trace)
    assert chip_reduce.op_seconds(trace, chip) == trace_reduce.op_seconds(trace)

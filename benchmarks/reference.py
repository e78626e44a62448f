"""The plain reference of one boosting step, and the comparison that decides
``correct`` for a training cell.

It imports nothing of the program and takes nothing the program has made
except the trees themselves, as ``Booster.dump_model()`` returns them: for
every tree the window's own calls grew, the reference routes every raw row
down the tree by ``value <= threshold``, computes the binary objective's
gradient and hessian from its own running score, sums them per leaf, and from
those sums works out what a correct booster must have written into that tree:

- each leaf's row count                                  (``count``: exact)
- each leaf's value ``-G / (H + lambda_l2) * learning_rate``, the first
  tree's with the boost-from-average score added          (``leaf_value``)
- each split's gain ``GL^2/HL + GR^2/HR - GP^2/HP``      (``split_gain``)
- the training log-loss after the last tree               (``loss``)
- where the job scores validation rows, their log-loss and AUC after the last
  tree, the rows routed down the same trees        (``valid_loss``, ``valid_auc``)

and then follows its own values into the next tree's gradients, so a tree is
judged against the state a correct booster would be in, not the program's.
That covers binning (thresholds are bin boundaries, and a row binned wrongly
lands in another leaf), gradients, histogram sums (leaf values and gains are
built from them; the row-weighted L2 gap of a tree's leaf values, and the gap
of the leaf that half of the rows sit in, which a few small leaves do not
move), the partition, the score update (the next tree's sums) and
the trees as the host holds them, and with validation rows the program's
traversal of rows it did not train on and its host metrics.  It does not show that a split is the best
one (PERF.md, Open questions).

Plain ``jax.numpy`` in float32, in row chunks so that it fits beside nothing
else on the device; per-chunk sums are added in float64 on the host.  The
control is this same code with ``precision="bfloat16"``: scores, gradients,
hessians, leaf values and gains rounded to bfloat16, sums still in float32,
which is what a bfloat16 histogram path would keep.  ``rows="half"`` leaves
every second row out, ``frozen=True`` never moves the score: the faults of a
training step, planted in the reference put in the program's place.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.work import children_first

CHUNK_ROWS = 1 << 20
SUB_ROWS = 4096          # rows summed in one float32 accumulation
MAX_LEAVES = 256


# ---------------------------------------------------------------- trees
def _f32_at_or_below(t: float) -> np.float32:
    """The largest float32 not above ``t``: ``x <= t`` for a float32-exact x
    is then the same in float32 as in float64."""
    f = np.float32(t)
    if float(f) > t:
        f = np.nextafter(f, np.float32(-np.inf), dtype=np.float32)
    return f


def parse_tree(tree_json: dict) -> dict:
    """Arrays of one dumped tree.  Children: >= 0 an internal node, < 0 the
    leaf ``~child``."""
    nl = int(tree_json["num_leaves"])
    ni = max(nl - 1, 0)
    t = {"num_leaves": nl,
         "feature": np.zeros(ni, np.int32), "threshold": np.zeros(ni, np.float64),
         "left": np.zeros(ni, np.int32), "right": np.zeros(ni, np.int32),
         "split_gain": np.zeros(ni, np.float64),
         "leaf_value": np.zeros(max(nl, 1), np.float64),
         "leaf_count": np.zeros(max(nl, 1), np.int64),
         "leaf_weight": np.full(max(nl, 1), np.nan),
         "leaf_is_right": np.zeros(max(nl, 1), bool), "depth": 0}
    if nl <= 1:
        t["leaf_value"][0] = float(tree_json["tree_structure"]["leaf_value"])
        return t
    stack = [(tree_json["tree_structure"], 1)]
    while stack:
        node, depth = stack.pop()
        i = int(node["split_index"])
        if node["decision_type"] != "<=" or node["missing_type"] == "Zero":
            raise ValueError("the reference routes numerical '<=' splits only")
        t["feature"][i] = node["split_feature"]
        t["threshold"][i] = node["threshold"]
        t["split_gain"][i] = node["split_gain"]
        t["depth"] = max(t["depth"], depth)
        for side in ("left", "right"):
            child = node[side + "_child"]
            if "split_index" in child:
                t[side][i] = int(child["split_index"])
                stack.append((child, depth + 1))
            else:
                leaf = int(child["leaf_index"])
                t[side][i] = ~leaf
                t["leaf_value"][leaf] = child["leaf_value"]
                t["leaf_count"][leaf] = child["leaf_count"]
                t["leaf_weight"][leaf] = child.get("leaf_weight", np.nan)
                t["leaf_is_right"][leaf] = side == "right"
    return t


# ---------------------------------------------------------------- device passes
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@jax.jit
def _leaf_of(x, feature, threshold, left, right, depth):
    """Leaf index of every row of ``x`` [B, F] by ``value <= threshold``."""
    cols = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]

    def step(_, node):
        at = jnp.maximum(node, 0)
        f = feature[at]
        v = jnp.sum(jnp.where(cols == f[:, None], x, 0.0), axis=1)
        nxt = jnp.where(v <= threshold[at], left[at], right[at])
        return jnp.where(node >= 0, nxt, node)

    node = jax.lax.fori_loop(0, depth, step,
                             jnp.zeros(x.shape[0], jnp.int32))
    return ~node


def _grad_hess(score, y):
    p = jax.nn.sigmoid(score)
    return p - y, p * (1.0 - p)


@jax.jit
def _leaf_sums(leaf, score, y, w, bf16):
    """Per-leaf [count, sum g, sum h] of one chunk, float32 over ``SUB_ROWS``
    rows at a time and over the sub-blocks after."""
    g, h = _grad_hess(score, y)
    g = jnp.where(bf16, _bf16(g), g)
    h = jnp.where(bf16, _bf16(h), h)
    data = jnp.stack([w, g * w, h * w], axis=-1).reshape(-1, SUB_ROWS, 3)
    onehot = jax.nn.one_hot(leaf.reshape(-1, SUB_ROWS), MAX_LEAVES,
                            dtype=jnp.float32)
    part = jnp.einsum("nbl,nbk->nlk", onehot, data,
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(part, axis=0)


@jax.jit
def _add_leaf_values(score, leaf, values, bf16):
    out = score + values[leaf]
    return jnp.where(bf16, _bf16(out), out)


@jax.jit
def _loss_sum(score, y, w):
    # log(1 + exp(-s)) for y = 1, log(1 + exp(s)) for y = 0
    per_row = w * jax.nn.softplus(jnp.where(y > 0.5, -score, score))
    return jnp.sum(per_row.reshape(-1, SUB_ROWS).sum(axis=1))


# ---------------------------------------------------------------- the reference
class Rows:
    """The raw matrix and labels on the device as float32 row chunks, the last
    one padded with rows of weight 0."""

    def __init__(self, x: np.ndarray, y: np.ndarray, chunk_rows: int = CHUNK_ROWS):
        n, f = x.shape
        chunk_rows = max(SUB_ROWS, min(chunk_rows, -(-n // SUB_ROWS) * SUB_ROWS))
        self.n, self.chunks = n, []
        for lo in range(0, n, chunk_rows):
            hi = min(n, lo + chunk_rows)
            xc = np.zeros((chunk_rows, f), np.float32)
            xc[:hi - lo] = x[lo:hi]
            yc = np.zeros(chunk_rows, np.float32)
            yc[:hi - lo] = y[lo:hi]
            wc = np.zeros(chunk_rows, np.float32)
            wc[:hi - lo] = 1.0
            self.chunks.append((jnp.asarray(xc), jnp.asarray(yc), jnp.asarray(wc)))


def auc_of(score: np.ndarray, label: np.ndarray) -> float:
    """Area under the ROC curve, rows of equal score counted half: the share of
    (positive, negative) pairs that the score orders rightly."""
    order = np.argsort(score, kind="stable")
    s, pos = score[order], label[order] > 0.5
    first = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])      # start of each run of ties
    pos_in = np.add.reduceat(pos.astype(np.float64), first)
    all_in = np.diff(np.r_[first, len(s)]).astype(np.float64)
    neg_in = all_in - pos_in
    neg_below = np.cumsum(neg_in) - neg_in
    n_pos, n_neg = pos_in.sum(), neg_in.sum()
    if n_pos == 0 or n_neg == 0:
        return float("nan")
    return float(np.sum(pos_in * (neg_below + 0.5 * neg_in)) / (n_pos * n_neg))


def follow(rows: Rows, trees: list, params: dict, precision: str = "float32",
           leave_out: str = "none", frozen: bool = False,
           valid: Rows | None = None) -> dict:
    """What a correct booster writes into ``trees``' structures: per tree the
    leaf counts, leaf values and split gains, the log-loss after the last, and
    the log-loss and AUC of the ``valid`` rows scored by the same trees.
    ``precision``, ``leave_out`` and ``frozen`` make it the control or a fault."""
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    bf16 = precision == "bfloat16"

    def rnd(a):
        if not bf16:
            return a
        return np.asarray(_bf16(jnp.asarray(a, jnp.float32)), np.float64)

    weights = []
    for _, _, w in rows.chunks:
        if leave_out == "half":
            w = w * (jnp.arange(w.shape[0]) % 2 == 0)
        weights.append(w)
    n_used = float(sum(float(jnp.sum(w)) for w in weights))
    pos = sum(float(jnp.sum(w * y)) for (_, y, _), w in zip(rows.chunks, weights))
    pavg = min(max(pos / n_used, 1e-15), 1.0 - 1e-15)
    init = float(rnd(np.float64(math.log(pavg / (1.0 - pavg)))))
    scores = [jnp.full(w.shape, init, jnp.float32) for w in weights]
    valid_scores = [jnp.full(w.shape, init, jnp.float32)
                    for _, _, w in (valid.chunks if valid else [])]

    out = {"init_score": init, "trees": []}
    for k, t in enumerate(trees):
        nl = t["num_leaves"]
        if nl > MAX_LEAVES:
            raise ValueError(f"the reference holds {MAX_LEAVES} leaves a tree")
        feature = jnp.asarray(t["feature"] if nl > 1 else np.zeros(1, np.int32))
        thr = jnp.asarray(np.array([_f32_at_or_below(v) for v in t["threshold"]]
                                   or [0.0], np.float32))
        left = jnp.asarray(t["left"] if nl > 1 else np.full(1, -1, np.int32))
        right = jnp.asarray(t["right"] if nl > 1 else np.full(1, -1, np.int32))
        sums = np.zeros((MAX_LEAVES, 3), np.float64)
        leaves = []
        for (x, y, _), w, s in zip(rows.chunks, weights, scores):
            leaf = _leaf_of(x, feature, thr, left, right, t["depth"])
            leaves.append(leaf)
            sums += np.asarray(_leaf_sums(leaf, s, y, w, bf16), np.float64)
        cnt, g, h = sums[:nl, 0], sums[:nl, 1], sums[:nl, 2]
        value = rnd(-g / (h + l2) * lr)
        # every internal node's sums from its leaves', children before parents
        ni = nl - 1
        node = np.zeros((ni, 3), np.float64)
        gain = np.zeros(ni, np.float64)

        def sums_of(child):
            return sums[~child] if child < 0 else node[child]

        for i in children_first(t):
            lo, hi = sums_of(int(t["left"][i])), sums_of(int(t["right"][i]))
            node[i] = lo + hi
            gain[i] = (lo[1] ** 2 / (lo[2] + l2) + hi[1] ** 2 / (hi[2] + l2)
                       - node[i][1] ** 2 / (node[i][2] + l2))
        out["trees"].append({"leaf_count": np.rint(cnt).astype(np.int64),
                             "leaf_value": value + (init if k == 0 else 0.0),
                             "leaf_hess": h, "split_gain": rnd(gain)})
        if not frozen:
            vals = jnp.asarray(np.pad(value, (0, MAX_LEAVES - nl)), jnp.float32)
            scores = [_add_leaf_values(s, leaf, vals, bf16)
                      for s, leaf in zip(scores, leaves)]
            valid_scores = [
                _add_leaf_values(s, _leaf_of(x, feature, thr, left, right, t["depth"]),
                                 vals, bf16)
                for (x, _, _), s in zip(valid.chunks if valid else [], valid_scores)]
    if valid:
        vloss = sum(float(_loss_sum(s, y, w))
                    for (_, y, w), s in zip(valid.chunks, valid_scores))
        out["valid_loss"] = vloss / valid.n
        score = np.concatenate([np.asarray(s)[np.asarray(w) > 0]
                                for (_, _, w), s in zip(valid.chunks, valid_scores)])
        label = np.concatenate([np.asarray(y)[np.asarray(w) > 0]
                                for _, y, w in valid.chunks])
        out["valid_auc"] = auc_of(score, label)
    loss = sum(float(_loss_sum(s, y, w))
               for (_, y, _), w, s in zip(rows.chunks, weights, scores))
    out["loss"] = loss / n_used
    return out


# ---------------------------------------------------------------- the comparison
def _rel_gaps(got, ref):
    """Each entry's |got - ref| over the larger of |ref| there and the median
    |ref|: some entries are all but zero."""
    scale = np.maximum(np.abs(ref), np.median(np.abs(ref)))
    gap = np.abs(got - ref) / np.where(scale > 0, scale, 1.0)
    return np.where(np.isfinite(gap), gap, np.inf)


def _worst_gap(got, ref):
    """The worst entry's relative gap."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    return float(np.max(_rel_gaps(got, ref))) if ref.size else 0.0


def _weighted_gap(got, ref, weight):
    """sqrt(sum w (got - ref)^2 / sum w ref^2): with rows as the weight, the
    relative L2 gap of what the tree adds to the scores of all rows."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    den = float(np.sum(weight * ref * ref))
    gap = math.sqrt(float(np.sum(weight * (got - ref) ** 2)) / den) if den else 0.0
    return gap if math.isfinite(gap) else float("inf")


def _median_gap(got, ref, weight):
    """The relative gap that half of the ``weight`` (rows) sits at or under.
    A few small leaves, which carry the float32 sums' error of their large
    siblings, do not move it; a loss of precision in every leaf does."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        return float("inf")
    if ref.size == 0:
        return 0.0
    gap = _rel_gaps(got, ref)
    order = np.argsort(gap)
    cum = np.cumsum(np.asarray(weight, np.float64)[order])
    return float(gap[order][np.searchsorted(cum, 0.5 * cum[-1])])


def compare(got: dict, ref: dict) -> dict:
    """The numbers compared, each the worst over the trees.  ``got`` is the
    program's record (``record_of_dump``) or a control's (``follow``).

    - ``count_gap``        worst leaf's |rows - reference rows|
    - ``leaf_value_gap``   row-weighted relative L2 gap of the leaf values
    - ``split_gain_gap``   sum |gain - reference gain| over the reference's sum
    - ``median_leaf_gap``  the relative gap of a leaf value that half of the
      rows sit at or under (against the larger of the leaf's own value and the
      median leaf's): the steady number, which a few small leaves do not move
    - ``loss_gap``         relative gap of the log-loss after the last tree
    - ``valid_loss_gap``, ``valid_auc_gap``  where both sides scored validation
      rows: relative gap of their log-loss, absolute gap of their AUC
    - ``worst_leaf_gap``, ``worst_split_gap``  the worst single leaf and split
      (against the larger of its own and the median one's size): a small right
      child carries the float32 sums' error of its large sibling (PERF.md
      section 6), so these swing and are printed, not compared.
    """
    init = ref["init_score"]
    out = dict.fromkeys(("count_gap", "leaf_value_gap", "split_gain_gap",
                         "median_leaf_gap",
                         "worst_leaf_gap", "worst_split_gap"), 0.0)
    if len(got["trees"]) != len(ref["trees"]):
        out = dict.fromkeys(out, float("inf"))
    for k, (a, b) in enumerate(zip(got["trees"], ref["trees"])):
        bias = init if k == 0 else 0.0     # judged without the first tree's bias
        if len(a["leaf_count"]) != len(b["leaf_count"]):
            gaps = dict.fromkeys(out, float("inf"))
        else:
            va, vb = np.asarray(a["leaf_value"]) - bias, b["leaf_value"] - bias
            ga, gb = np.asarray(a["split_gain"], np.float64), b["split_gain"]
            total = float(np.sum(np.abs(gb)))
            gaps = {
                "count_gap": float(np.max(np.abs(
                    np.asarray(a["leaf_count"], np.int64) - b["leaf_count"]))),
                "leaf_value_gap": _weighted_gap(va, vb, b["leaf_count"]),
                "split_gain_gap": (float(np.sum(np.abs(ga - gb))) / total
                                   if total else 0.0),
                "median_leaf_gap": _median_gap(va, vb, b["leaf_count"]),
                "worst_leaf_gap": _worst_gap(va, vb),
                "worst_split_gap": _worst_gap(ga, gb)}
        for name, v in gaps.items():
            out[name] = max(out[name], v if math.isfinite(v) else float("inf"))
    out["loss_gap"] = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    if "valid_loss" in ref:
        out["valid_loss_gap"] = (abs(got.get("valid_loss", float("inf")) - ref["valid_loss"])
                                 / abs(ref["valid_loss"]))
        out["valid_auc_gap"] = abs(got.get("valid_auc", float("inf")) - ref["valid_auc"])
    return out


def per_tree(got: dict, ref: dict) -> list:
    """For an earlier line: each tree's row-weighted gap, and its worst leaf
    with that leaf's rows and both values."""
    init, out = ref["init_score"], []
    for k, (a, b) in enumerate(zip(got["trees"], ref["trees"])):
        bias = init if k == 0 else 0.0
        va, vb = np.asarray(a["leaf_value"]) - bias, b["leaf_value"] - bias
        if va.shape != vb.shape:
            continue
        gap = _rel_gaps(va, vb)
        j = int(np.argmax(gap))
        entry = {"weighted": _weighted_gap(va, vb, b["leaf_count"]),
                 "median": _median_gap(va, vb, b["leaf_count"]),
                 "worst": float(gap[j]), "leaves_over_1pct": int(np.sum(gap > 1e-2)),
                 "rows": int(b["leaf_count"][j]), "min_rows": int(b["leaf_count"].min()),
                 "got": float(va[j]), "ref": float(vb[j])}
        if "leaf_weight" in a and "leaf_hess" in b:    # the program's own sums
            entry.update(hess_got=float(a["leaf_weight"][j]), hess_ref=float(b["leaf_hess"][j]),
                         is_right_child=bool(a["leaf_is_right"][j]),
                         right_children_over_1pct=int(np.sum((gap > 1e-2) & a["leaf_is_right"])))
        out.append(entry)
    return out


def record_of_dump(trees: list, loss: float, valid: dict | None = None) -> dict:
    """The program's own statement: its dumped trees, its own training loss and
    what its last ``eval_valid()`` said (``{"binary_logloss", "auc"}``)."""
    out = {"trees": [{"leaf_count": t["leaf_count"][:max(t["num_leaves"], 1)],
                      "leaf_value": t["leaf_value"][:max(t["num_leaves"], 1)],
                      "leaf_weight": t["leaf_weight"][:max(t["num_leaves"], 1)],
                      "leaf_is_right": t["leaf_is_right"][:max(t["num_leaves"], 1)],
                      "split_gain": t["split_gain"]} for t in trees],
           "loss": float(loss)}
    if valid is not None:
        out["valid_loss"] = float(valid["binary_logloss"])
        out["valid_auc"] = float(valid["auc"])
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number compared is finite
    and at or under the configuration's limit for it.  Each number has to have
    a limit, but for the two printed ones (``worst_*``); a limit for a number
    that this traffic mix does not produce is not used."""
    held = [k for k in numbers if not k.startswith("worst_")]
    table = {k: {"value": float(numbers[k]), "limit": float(limits[k])} for k in held}
    ok = bool(table) and all(math.isfinite(e["value"]) and e["value"] <= e["limit"]
                             for e in table.values())
    return ok, table

"""The plain reference of a boosting step whose rows lie on several chips.

It follows the program's trees as ``reference.follow`` does and judges them
by the same comparison (``reference.compare``, ``reference.verdict``), but
each shard's rows stay on a chip of their own, and the chips work at once: a
tree costs what one shard costs, not the sum of the shards.

Per shard and tree, in float32 row chunks: every row is routed to its leaf
by ``value <= threshold`` with no per-row lookup (the chunk is held
feature-major, so the split columns are contiguous rows of it; a leaf is the
one whose path's conditions all hold, counted by one 0/1 matrix product),
its gradient and hessian come from its own running score, and the leaf sums
are ``reference._leaf_sums``': float32 within 4,096-row blocks.  The host
adds every chunk of every shard in float64, so the sums do not depend on how
the rows are divided.  The init score, the loss and the validation AUC are
over all shards' rows.  Plain ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; nothing of the program is
imported, and nothing it made is read but the dumped trees.
"""
from __future__ import annotations

import math
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import reference
from benchmarks.work import children_first
from benchmarks.reference import (CHUNK_ROWS, MAX_LEAVES, SUB_ROWS, _add_leaf_values,
                                  _bf16, _f32_at_or_below, _leaf_sums, _loss_sum)


class Shards:
    """Each shard's raw rows on its own device (``devices[s % len]``) as
    float32 chunks ``[F, CHUNK_ROWS]`` with labels and weights, the last chunk
    of a shard padded with rows of weight 0.  The host casts and transposes
    the chunks on all its cores at once."""

    def __init__(self, xs: list, ys: list, devices=None, chunk_rows: int = CHUNK_ROWS):
        devices = devices or jax.devices()
        self.n = sum(len(y) for y in ys)

        def place(job):
            s, lo, rows = job
            x, y, dev = xs[s], ys[s], devices[s % len(devices)]
            hi = min(len(y), lo + rows)
            xt = np.zeros((x.shape[1], rows), np.float32)
            xt[:, :hi - lo] = x[lo:hi].T
            yc = np.zeros(rows, np.float32)
            yc[:hi - lo] = y[lo:hi]
            wc = np.zeros(rows, np.float32)
            wc[:hi - lo] = 1.0
            return tuple(jax.device_put(a, dev) for a in (xt, yc, wc))

        jobs = []
        for s, y in enumerate(ys):
            rows = max(SUB_ROWS, min(chunk_rows, -(-len(y) // SUB_ROWS) * SUB_ROWS))
            jobs += [(s, lo, rows) for lo in range(0, len(y), rows)]
        with ThreadPoolExecutor(max(1, min(len(jobs), os.cpu_count() or 1))) as pool:
            placed = list(pool.map(place, jobs))
        self.shards = [[c for (s, _, _), c in zip(jobs, placed) if s == shard]
                       for shard in range(len(ys))]

    @property
    def chunks(self):
        return [c for shard in self.shards for c in shard]


def paths(tree: dict):
    """``(M [nodes, MAX_LEAVES] int8, need [MAX_LEAVES] int32)``: ``M[i, j]``
    is +1 where leaf ``j`` lies left of node ``i``, -1 right of it; a row is
    in leaf ``j`` when, over the nodes, ``sum M[i, j] * left_i(row)`` reaches
    ``need[j]``, the number of left turns on ``j``'s path."""
    nl = tree["num_leaves"]
    m = np.zeros((max(nl - 1, 1), MAX_LEAVES), np.int8)
    need = np.full(MAX_LEAVES, -1, np.int32)      # no row reaches an unused leaf
    if nl <= 1:
        need[0] = 0
        return m, need
    stack = [(0, [])]
    while stack:
        node, above = stack.pop()
        for side, sign in (("left", 1), ("right", -1)):
            child = int(tree[side][node])
            path = above + [(node, sign)]
            if child >= 0:
                stack.append((child, path))
                continue
            for i, s in path:
                m[i, ~child] = s
            need[~child] = sum(1 for _, s in path if s > 0)
    return m, need


@jax.jit
def _leaf_of(xt, feature, threshold, m, need):
    """Leaf of every row of the feature-major chunk ``xt`` [F, B]."""
    left = (xt[feature] <= threshold[:, None]).astype(jnp.bfloat16)   # [nodes, B]
    score = jnp.dot(m.astype(jnp.bfloat16).T, left,
                    preferred_element_type=jnp.float32)          # [leaves, B]
    # a row meets every left turn of its own leaf's path and no right turn;
    # any other leaf misses a left turn or counts a right one against it
    return jnp.argmax(score == need[:, None].astype(jnp.float32), axis=0).astype(jnp.int32)


def follow(rows: Shards, trees: list, params: dict, precision: str = "float32",
           leave_out: str = "none", frozen: bool = False,
           valid: Shards | None = None) -> dict:
    """``reference.follow`` over rows in shards: the same record (per tree
    the leaf counts, values, hessian sums and split gains; the loss; the
    validation log-loss and AUC)."""
    with jax.default_matmul_precision("highest"):
        return _follow(rows, trees, params, precision, leave_out, frozen, valid)


def _follow(rows, trees, params, precision, leave_out, frozen, valid):
    lr = float(params.get("learning_rate", 0.1))
    l2 = float(params.get("lambda_l2", 0.0))
    bf16 = precision == "bfloat16"

    def rnd(a):
        if not bf16:
            return a
        return np.asarray(_bf16(jnp.asarray(a, jnp.float32)), np.float64)

    train = rows.chunks
    weights = [w * (jnp.arange(w.shape[0]) % 2 == 0) if leave_out == "half" else w
               for _, _, w in train]
    n_used = float(sum(float(jnp.sum(w)) for w in weights))
    pos = sum(float(jnp.sum(w * y)) for (_, y, _), w in zip(train, weights))
    pavg = min(max(pos / n_used, 1e-15), 1.0 - 1e-15)
    init = float(rnd(np.float64(math.log(pavg / (1.0 - pavg)))))
    scores = [jnp.full(w.shape, init, jnp.float32, device=w.device) for w in weights]
    vchunks = valid.chunks if valid else []
    vscores = [jnp.full(w.shape, init, jnp.float32, device=w.device) for _, _, w in vchunks]

    out = {"init_score": init, "trees": []}
    for k, t in enumerate(trees):
        nl = t["num_leaves"]
        if nl > MAX_LEAVES:
            raise ValueError(f"the reference holds {MAX_LEAVES} leaves a tree")
        m, need = paths(t)
        feature = t["feature"] if nl > 1 else np.zeros(1, np.int32)
        thr = np.array([_f32_at_or_below(v) for v in t["threshold"]] or [0.0], np.float32)
        tables = {}

        def on(dev):
            if dev not in tables:
                tables[dev] = tuple(jax.device_put(a, dev) for a in (feature, thr, m, need))
            return tables[dev]

        # every chunk of every shard is issued before any is read back, so
        # the shards' chips run together
        leaves = [_leaf_of(x, *on(x.device)) for x, _, _ in train]
        parts = [_leaf_sums(leaf, s, y, w, bf16)
                 for leaf, s, (_, y, _), w in zip(leaves, scores, train, weights)]
        sums = np.zeros((MAX_LEAVES, 3), np.float64)
        for p in parts:
            sums += np.asarray(p, np.float64)
        cnt, g, h = sums[:nl, 0], sums[:nl, 1], sums[:nl, 2]
        value = rnd(-g / (h + l2) * lr)
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
            vals = np.pad(value, (0, MAX_LEAVES - nl)).astype(np.float32)
            scores = [_add_leaf_values(s, leaf, jax.device_put(vals, s.device), bf16)
                      for s, leaf in zip(scores, leaves)]
            vscores = [_add_leaf_values(s, _leaf_of(x, *on(x.device)),
                                        jax.device_put(vals, s.device), bf16)
                       for (x, _, _), s in zip(vchunks, vscores)]
    if valid:
        vloss = sum(float(_loss_sum(s, y, w)) for (_, y, w), s in zip(vchunks, vscores))
        out["valid_loss"] = vloss / valid.n
        score = np.concatenate([np.asarray(s)[np.asarray(w) > 0]
                                for (_, _, w), s in zip(vchunks, vscores)])
        label = np.concatenate([np.asarray(y)[np.asarray(w) > 0] for _, y, w in vchunks])
        out["valid_auc"] = reference.auc_of(score, label)
    loss = sum(float(_loss_sum(s, y, w))
               for (_, y, _), w, s in zip(train, weights, scores))
    out["loss"] = loss / n_used
    return out

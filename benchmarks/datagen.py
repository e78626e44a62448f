"""Training data from ``--seed``: one general generator, driven by the ``data``
block of a configuration's file.

A block lists column groups, each ``{"kind", "count", ...parameters}``, and a
``label`` model.  Every column is a monotone function of a standard normal of
its own, which loads on one latent shared by the row (``mix``), so columns are
correlated the way features built from one impression are.  Kinds, each over
``v = mu + sigma * z`` with ``mu`` and ``sigma`` spread evenly over the group:

- ``count``    integer-valued, heavy-tailed, zero included: ``floor(exp(v))``
- ``rate``     in (0, 1): the logistic of ``v``
- ``logcount`` non-negative: ``log1p(exp(v))``
- ``normal``   ``v`` itself

The label is Bernoulli of a nonlinear logistic model over ``label.features``
of the columns' normals; its intercept is solved so that the positive rate is
``label.rate``.

The population is the configuration's and the sample is the seed's.  The
label model (which columns carry the label, their weights, the pairwise terms)
is drawn from ``label.model_seed``, a key of the block, and its intercept is
solved on block ``[model_seed, 0, 0]``: one truth behind the labels for every
``--seed``.  ``--seed`` seeds every block of rows (``[seed, stream, i]``);
``stream`` draws a disjoint set of rows (0 the training rows, 1 the validation
rows).  So two seeds are two samples of one population, as two days of one
click log are: the trees grown on them have one shape, where two label models
grow trees whose cost differs by a tenth (PERF.md section 2).  A block without
``model_seed`` raises: there is no fall-back to the seed.

The matrix is written block by block straight into one C-contiguous float64
array (what ``lgb.Dataset`` takes without another copy); values are float32,
so a reference that holds the matrix in float32 sees the same numbers.  Blocks
are seeded one by one, so the result does not depend on the thread count.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 262_144


def _groups(spec):
    """Per-group (kind, first column, last column + 1, mu [k,1], sigma [k,1])."""
    groups, at = [], 0
    for group in spec["columns"]:
        k = int(group["count"])
        mu = np.linspace(group.get("mu_lo", 0.0), group.get("mu_hi", 0.0), k)
        sg = np.linspace(group.get("sigma_lo", 1.0), group.get("sigma_hi", 1.0), k)
        groups.append((group["kind"], at, at + k,
                       mu.astype(np.float32)[:, None],
                       sg.astype(np.float32)[:, None]))
        at += k
    return groups, at


def _value(kind, v):
    if kind == "count":
        return np.floor(np.exp(np.minimum(v, 16.0)))
    if kind == "rate":
        return 1.0 / (1.0 + np.exp(-v))
    if kind == "logcount":
        return np.log1p(np.exp(np.minimum(v, 16.0)))
    if kind == "normal":
        return v
    raise ValueError(f"unknown column kind {kind!r}")


def _label_weights(spec, n_cols, seed):
    """Weights of the label's logistic model: linear terms on the columns'
    normals, a few pairwise products and one threshold term."""
    rng = np.random.default_rng([int(seed), 0x1ABE1])
    k = int(spec["label"]["features"])
    feats = rng.choice(n_cols, size=k, replace=False)
    w = rng.normal(0.0, 1.0, size=k).astype(np.float32)
    pairs = rng.choice(k, size=(max(1, k // 3), 2))
    wp = rng.normal(0.0, 0.7, size=len(pairs)).astype(np.float32)
    return feats, w, pairs, wp


def _block(spec, groups, f, weights, key, rows):
    """(values float32 [F, rows], the label's logit without its intercept, the
    uniforms the label is drawn with) of one block.  Column-major, so a column
    is contiguous; plain ufuncs, which run in parallel across the threads that
    fill blocks."""
    rng = np.random.default_rng(np.random.SeedSequence(key))
    z = rng.standard_normal((f, rows), dtype=np.float32)
    latent = rng.standard_normal((1, rows), dtype=np.float32)
    mix = np.float32(spec.get("mix", 0.5))
    z *= np.float32(np.sqrt(1.0 - mix * mix))
    z += mix * latent
    x32 = np.empty((f, rows), np.float32)
    for kind, a, b, mu, sigma in groups:
        x32[a:b] = _value(kind, mu + sigma * z[a:b])
    feats, w, pairs, wp = weights
    zs = z[feats]
    logit = w @ zs
    for (a, b), wab in zip(pairs, wp):
        logit += wab * zs[a] * zs[b]
    logit += np.float32(1.5) * (zs[0] > 1.0)
    logit *= np.float32(spec["label"].get("scale", 0.6))
    return x32, logit, rng.random(rows, dtype=np.float32)


def label_model(spec: dict):
    """The population's label model, from ``label.model_seed`` alone:
    (columns, weights, pairs, pair weights, intercept).  The intercept is
    solved on the first block of rows that the model's own seed draws
    (bisection on the mean of the sigmoid), so both streams of every seed
    share it."""
    if "model_seed" not in spec["label"]:
        raise KeyError("the data block's label has no model_seed: the label model "
                       "belongs to the configuration, not to --seed")
    model_seed = int(spec["label"]["model_seed"])
    groups, f = _groups(spec)
    weights = _label_weights(spec, f, model_seed)
    _, head, _ = _block(spec, groups, f, weights, [model_seed, 0, 0], BLOCK_ROWS)
    head = head.astype(np.float64)
    lo, hi = -30.0, 30.0
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if np.mean(1.0 / (1.0 + np.exp(-(head + mid)))) < float(spec["label"]["rate"]):
            lo = mid
        else:
            hi = mid
    return (*weights, np.float32(0.5 * (lo + hi)))


def make(spec: dict, rows: int, seed: int, stream: int = 0,
         threads: int | None = None):
    """(X float64 [rows, F] C-contiguous, y float32 [rows]): ``rows`` rows of
    the configuration's population, drawn by ``seed``."""
    seed = int(seed)
    groups, f = _groups(spec)
    *weights, intercept = label_model(spec)
    n_full, rest = divmod(rows, BLOCK_ROWS)
    sizes = [BLOCK_ROWS] * n_full + ([rest] if rest else [])

    x = np.empty((rows, f), np.float64)
    y = np.empty(rows, np.float32)

    def fill(i):
        at = i * BLOCK_ROWS
        x32, logit, u = _block(spec, groups, f, weights, [seed, stream, i], sizes[i])
        x[at:at + sizes[i]] = x32.T
        y[at:at + sizes[i]] = u < 1.0 / (1.0 + np.exp(-(logit + intercept)))

    threads = threads or min(max(len(sizes), 1), max(1, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(len(sizes))))
    return x, y

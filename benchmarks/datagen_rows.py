"""A row range of ``datagen.make``'s table, made without the rest of it.

``datagen.make(spec, n, seed, stream)`` fills the table block by block, each
block seeded ``[seed, stream, i]`` and ``BLOCK_ROWS`` long but the last,
which holds what is left of ``n``.  Rows ``[lo, hi)`` therefore need only the
blocks that hold them (each drawn whole, at its size in the ``n``-row table)
and the configuration's label model: a job that holds a table in shards
makes each shard alone, and the shards laid end to end are, byte for byte,
``make(spec, n, seed, stream)``.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks import datagen


def make_rows(spec: dict, n: int, lo: int, hi: int, seed: int, stream: int = 0,
              threads: int | None = None, model=None):
    """(X float64 [hi - lo, F] C-contiguous, y float32 [hi - lo]): rows
    ``lo`` to ``hi`` of the ``n``-row table ``datagen.make`` draws by
    ``seed`` and ``stream``.  ``model`` is ``datagen.label_model(spec)``,
    for a caller that makes several ranges of one population."""
    if not 0 <= lo <= hi <= n:
        raise ValueError(f"rows [{lo}, {hi}) are not in a table of {n}")
    block = datagen.BLOCK_ROWS
    groups, f = datagen._groups(spec)
    *weights, intercept = model or datagen.label_model(spec)
    x = np.empty((hi - lo, f), np.float64)
    y = np.empty(hi - lo, np.float32)

    def fill(i):
        start = i * block
        size = min(block, n - start)
        x32, logit, u = datagen._block(spec, groups, f, weights,
                                       [int(seed), stream, i], size)
        a, b = max(lo, start), min(hi, start + size)
        x[a - lo:b - lo] = x32.T[a - start:b - start]
        y[a - lo:b - lo] = (u < 1.0 / (1.0 + np.exp(-(logit + intercept))))[
            a - start:b - start]

    first, last = lo // block, -(-hi // block)
    threads = threads or min(max(last - first, 1),
                             max(1, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(first, last)))
    return x, y


def shard_bounds(n: int, shards: int) -> list:
    """``[(lo, hi)]`` of ``shards`` row ranges of ``n`` rows, laid end to end,
    as the data-parallel mesh holds them: ``ceil(n / shards)`` rows each but
    the last."""
    per = -(-n // shards)
    return [(min(n, s * per), min(n, (s + 1) * per)) for s in range(shards)]

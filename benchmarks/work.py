"""The least work a tree needs, from shapes and the grown tree's own counts,
and the chip's peaks.

Whatever builds a leaf-wise tree over binned rows has to read, for the root
and then for the smaller child of every split (the larger one comes by
subtraction), each row's bin of every column and its gradient and hessian:
``columns`` bytes of bins (one byte a bin up to 256 bins) and 8 bytes of
float32 gradient and hessian.  That traffic over the chip's memory bandwidth
is the least time; the arithmetic (two adds a bin cell) is far under the
chip's compute peak, so memory is what bounds it.
"""
from __future__ import annotations

import json
import os

GRADIENT_BYTES = 8          # float32 gradient + float32 hessian per row


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind raises."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def children_first(tree: dict) -> list:
    """Internal nodes of a parsed tree, every child before its parent."""
    order, stack = [], [0] if tree["num_leaves"] > 1 else []
    while stack:
        i = stack.pop()
        order.append(i)
        stack += [int(c) for c in (tree["left"][i], tree["right"][i]) if c >= 0]
    return order[::-1]


def rows_read(tree: dict) -> int:
    """Rows a histogram pass has to touch for one grown tree: all rows at the
    root, then the smaller child of each split."""
    count = {}

    def rows_of(child):
        return int(tree["leaf_count"][~child]) if child < 0 else count[child]

    total = 0
    for i in children_first(tree):
        lo, hi = rows_of(int(tree["left"][i])), rows_of(int(tree["right"][i]))
        count[i] = lo + hi
        total += min(lo, hi)
    return total + (count[0] if count else int(tree["leaf_count"][0]))


def least_bytes(tree: dict, columns: int, bin_bytes: int = 1) -> int:
    return rows_read(tree) * (columns * bin_bytes + GRADIENT_BYTES)


def least_seconds(trees, columns: int, device_kind: str) -> float:
    """Seconds the chip's memory needs to deliver the least bytes of ``trees``."""
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    return sum(least_bytes(t, columns) for t in trees) / bw

"""What the window's trees look like, from the counters the program puts on
its ``lgbm/update/drain`` spans (``GBDT._count_leaves``: ``leaves``,
``leaves_under_100_rows``, ``tree_depth``, ``min_leaf_hessian``), and the
device time under a scope that ``phase_reduce.SHARES`` does not name.

The window's trees are found as ``phase_reduce.reduce`` finds them: the
``lgbm/update`` spans paired with the window's ``bench/update`` annotations.
Against a program whose drain spans carry no such counter or that names no
such scope (an older commit), and in a run with no device plane (a rehearsal
on the CPU), every function returns None.
"""
from __future__ import annotations

from benchmarks import phase_reduce


def window_drains(run: dict) -> list | None:
    """The arguments of the drain spans of the window's trees that carry the
    leaf counters."""
    obs = phase_reduce._program()
    if obs is None or run.get("trace") is None:
        return None
    spans = [s.as_dict() for s in obs.get_tracer().spans()]
    clock = phase_reduce.clock_offset(run["trace"], spans)
    if clock is None:
        return None
    its = {s["iteration"] for s in clock[2]}
    drains = [s["args"] for s in spans if s["name"] == "lgbm/update/drain"
              and s["args"] and s["args"].get("tree_iteration") in its
              and "leaves" in s["args"]]
    return drains or None


def small_leaf_share(drains: list) -> float:
    """Leaves under 100 rows over all leaves, in percent."""
    return 100.0 * sum(d["leaves_under_100_rows"] for d in drains) \
        / sum(d["leaves"] for d in drains)


def mean_tree_depth(drains: list) -> float:
    return sum(d["tree_depth"] for d in drains) / len(drains)


def scope_share(run: dict, scope: str):
    """Share of the window in operations under ``scope`` (self time, Mosaic
    calls left out), in percent; 0.0 where the program names the scope and
    no operation carries it (XLA fused them into a neighbour's)."""
    table = phase_reduce.table(run)
    obs = phase_reduce._program()
    if table is None or scope not in getattr(obs.scopes, "SCOPES", ()):
        return None
    return 100.0 * table["scope_seconds"].get(scope, 0.0) / run["window_s"]

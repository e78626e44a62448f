"""From a device trace and what the program kept in its own memory to the
time by phase.

The trace (``trace_reduce.read_profile``) names a device operation by its HLO
line and keeps of the host only the ``bench/...`` annotations.  The program
(``lightgbm_tpu.obs``) keeps three things in-process, with no parameter:

- ``device_scopes()``: ``{key: scope}``, where ``key`` is an operation's name
  and result shape as ``obs.scopes.op_key`` reads them off the HLO line and
  ``scope`` the ``lgbm/...`` name a ``jax.named_scope`` gave it ("" for none,
  "ambiguous" where two programs share a key);
- ``get_tracer().spans()``: host spans (``lgbm/update``, ``lgbm/eval``,
  ``lgbm/compile``, ...) on ``time.time_ns()``, each with its parent, its
  boosting iteration and its arguments (``lgbm/update/drain`` carries the
  frontier grower's counters of the tree it waited for);

``table(run)`` joins them once a run and every reader under
``layer_metrics/`` takes one number from it.  The spans are put on the
trace's clock by the pairs (``bench/update`` annotation, ``lgbm/update``
span) of the window's iterations: the median offset, and no table at all
where a pair is more than ``MAX_RESIDUAL_NS`` off it, so a broken clock shows
as missing metrics and not as wrong ones.  Against a program that has no
``device_scopes`` (an older commit), and in a run with no device plane (a
rehearsal on the CPU), ``table`` is None and every reader returns None.
"""
from __future__ import annotations

import json
import os
import statistics

from benchmarks.trace_reduce import _union, op_seconds, window_of

MAX_RESIDUAL_NS = 1_000_000
ROUND = "lgbm/frontier_round/"
# metric -> the scopes whose operations it sums (self time, Mosaic calls
# left out: they are hist_kernel_share's)
SHARES = {
    "partition_share": (ROUND + "partition", ROUND + "partition/decide",
                        ROUND + "partition/rank", ROUND + "partition/scatter"),
    "hist_gather_share": (ROUND + "hist_gather",),
    "split_search_share": ("lgbm/split_search",),
    "round_select_share": (ROUND + "select", ROUND + "bookkeeping",
                           "lgbm/finalize"),
    "boost_step_share": ("lgbm/gradients", "lgbm/sample", "lgbm/score_update",
                         "lgbm/valid_traverse"),
}
UNSCOPED = ("", "ambiguous", "unknown")     # unknown: a key not in the table
_READ = {s for which in SHARES.values() for s in which} | set(UNSCOPED) \
    | {"mosaic"}


def _program():
    """``lightgbm_tpu.obs`` if it has what this reads, else None."""
    try:
        from lightgbm_tpu import obs
    except Exception:
        return None
    if not hasattr(obs, "device_scopes") or not hasattr(obs, "scopes"):
        return None
    return obs


def scope_seconds(ops: dict, scopes: dict, op_key, kernel_words) -> dict:
    """{scope: self seconds} of ``ops`` ({operation: self seconds}); the
    Mosaic calls under "mosaic", operations whose key the table lacks under
    "unknown"."""
    out = {}
    for name, sec in ops.items():
        if any(w in name for w in kernel_words):
            scope = "mosaic"
        else:
            scope = scopes.get(op_key(name) or name, "unknown")
        out[scope] = out.get(scope, 0.0) + sec
    return out


def clock_offset(trace: dict, spans: list):
    """(offset_ns, residual_ns, pairs) that puts a span's ``time.time_ns()``
    on the trace's clock (``trace = span + offset``): the window's
    ``bench/update`` annotations against the last as many ``lgbm/update``
    spans.  None where there is no pair or one lies over MAX_RESIDUAL_NS off
    the median."""
    anns = [e for e in trace["host"] if e[0] == "bench/update"]
    ups = [s for s in spans if s["name"] == "lgbm/update"][-len(anns):]
    if not anns or len(ups) != len(anns):
        return None
    offs = [a[1] - s["start"] for a, s in zip(anns, ups)]
    mid = int(statistics.median(offs))
    residual = max(abs(o - mid) for o in offs)
    if residual > MAX_RESIDUAL_NS:
        return None
    return mid, residual, ups


def idle_gaps(trace: dict) -> list:
    """[(start_ns, end_ns)] in which the first chip ran nothing, inside the
    window."""
    lo, hi = window_of(trace)
    events = next(iter(trace["device"].values()))
    busy = _union([(max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def name_gap(gap, spans, offset) -> str:
    """The deepest span that covers most of the gap (over half of it), else
    the span that covers the most of it, else "no span"."""
    a, b = gap
    best, best_key = "no span", (0, -1, 0)
    for s in spans:
        cover = min(b, s["end"] + offset) - max(a, s["start"] + offset)
        if cover <= 0:
            continue
        most = 2 * cover > b - a
        key = (1, s["depth"], cover) if most else (0, 0, cover)
        if key > best_key:
            best, best_key = s["name"], key
    return best


def unattributed_ns(gaps, spans, offset) -> int:
    """Idle nanoseconds under no leaf span (a span that is no span's
    parent)."""
    parents = {s["parent"] for s in spans}
    leaves = [(s["start"] + offset, s["end"] + offset) for s in spans
              if s["id"] not in parents]
    total = 0
    for a, b in gaps:
        covered = _union([(max(a, lo), min(b, hi)) for lo, hi in leaves
                          if hi > a and lo < b])
        total += (b - a) - sum(hi - lo for lo, hi in covered)
    return total


def _total(spans, *names) -> float:
    return sum((s["end"] - s["start"]) / 1e9 for s in spans
               if s["name"] in names)


def _within(spans, lo, hi, name) -> float:
    """Seconds of the spans called ``name`` that lie inside [lo, hi)."""
    return sum((min(s["end"], hi) - max(s["start"], lo)) / 1e9 for s in spans
               if s["name"] == name and s["end"] > lo and s["start"] < hi)


def reduce(trace: dict, window_s: float, scopes: dict, spans: list, op_key,
           kernel_words) -> dict | None:
    """The table from plain data (what the tests feed it): ``metrics`` by
    name without the ``.train`` suffix, and what is printed besides."""
    clock = clock_offset(trace, spans)
    if clock is None:
        return None
    offset, residual, updates = clock
    ops = op_seconds(trace)
    by_scope = scope_seconds(ops, scopes, op_key, kernel_words)
    metrics = {name: 100.0 * sum(by_scope.get(s, 0.0) for s in which) / window_s
               for name, which in SHARES.items()}
    metrics["unscoped_share"] = 100.0 * sum(
        by_scope.get(s, 0.0) for s in UNSCOPED) / window_s

    lo, hi = (t - offset for t in window_of(trace))    # on the spans' clock
    trees = len(updates)
    its = {s["iteration"] for s in updates}
    drains = [s["args"] for s in spans if s["name"] == "lgbm/update/drain"
              and s["args"] and s["args"].get("tree_iteration") in its
              and "rounds" in s["args"]]
    if drains:
        metrics["frontier_rounds_per_tree"] = \
            sum(d["rounds"] for d in drains) / len(drains)
        passed = sum(d["rows_passed"] for d in drains)
        if passed:
            metrics["partition_useful_row_share"] = \
                100.0 * sum(d["rows_selected"] for d in drains) / passed
    pre = "lgbm/dataset/construct/"
    metrics["find_bins_s"] = _total(spans, pre + "find_bins")
    metrics["bin_values_s"] = _total(spans, pre + "bin_values",
                                     pre + "reference_bin",
                                     pre + "to_2d_float")
    metrics["election_s"] = _total(spans, "lgbm/booster/init/election")
    compiles = [s for s in spans if s["name"] == "lgbm/compile"]
    metrics["compile_load_s"] = sum(
        (s["end"] - s["start"]) / 1e9 for s in compiles if s["end"] <= lo)
    in_window = [s for s in compiles if lo <= s["start"] < hi]
    metrics["compiles_in_window"] = len(in_window)
    # the host's own work on the metrics: lgbm/eval less the wait for the
    # device to finish the tree whose scores it reads
    metrics["host_metric_s_per_tree"] = (
        _within(spans, lo, hi, "lgbm/eval")
        - _within(spans, lo, hi, "lgbm/eval/wait")) / trees

    gaps = idle_gaps(trace)
    idle = sum(b - a for a, b in gaps)
    metrics["idle_unattributed_share"] = \
        100.0 * unattributed_ns(gaps, spans, offset) / idle if idle else 0.0
    by_id = {s["id"]: s["name"] for s in spans}
    return {
        "metrics": metrics,
        "scope_seconds": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        # the longest operations with their scopes: what PERF.md needs to
        # name the ledger's ``breakdown.device_ops`` by phase
        "ops_by_scope": [
            [n[:100], sec, scopes.get(op_key(n) or n, "unknown")]
            for n, sec in sorted(ops.items(), key=lambda kv: -kv[1])[:30]],
        # scoped, outside the Mosaic calls, and in no share above: the root's
        # sums, the histogram subtraction, the loop's own shell
        "seconds_no_share_reads": sum(
            sec for scope, sec in by_scope.items()
            if scope not in _READ),
        "idle_gaps": [[name_gap(g, spans, offset), (g[1] - g[0]) / 1e9]
                      for g in sorted(gaps, key=lambda g: g[0] - g[1])[:10]],
        "idle_s": idle / 1e9,
        "clock": {"offset_ns": offset, "residual_ns": residual,
                  "pairs": trees},
        "compiles_in_window": [
            {**(s["args"] or {}), "parent": by_id.get(s["parent"])}
            for s in in_window],
        "spans": {"kept": len(spans),
                  "in_window": sum(1 for s in spans if lo <= s["start"] < hi)},
        # every span name's seconds over the whole process and inside the
        # window: set-up by step, and what a tree costs the host
        "span_seconds": {
            name: [_total(spans, name), _within(spans, lo, hi, name)]
            for name in sorted({s["name"] for s in spans})},
    }


def table(run: dict) -> dict | None:
    """``reduce`` over this run and this process, once; printed as one JSON
    line (before the result line) the first time."""
    if "_phase_table" in run:
        return run["_phase_table"]
    run["_phase_table"] = out = None
    obs = _program()
    if obs is not None and run.get("trace") is not None:
        with open(os.path.join(os.path.dirname(__file__), "kernels.json")) as f:
            words = [w for ws in json.load(f).values() for w in ws]
        spans = [s.as_dict() for s in obs.get_tracer().spans()]
        run["_phase_table"] = out = reduce(
            run["trace"], run["window_s"], obs.device_scopes(), spans,
            obs.scopes.op_key, words)
        print(json.dumps({"phase_table": out}, default=str), flush=True)
    return out


def value(run: dict, metric: str):
    """One metric of the table, or None."""
    out = table(run)
    return None if out is None else out["metrics"].get(metric)

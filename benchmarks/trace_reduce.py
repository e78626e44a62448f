"""From a profiler trace to numbers: device busy time, time by operation,
idle gaps and what the host was doing in them.

``read_profile`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a
plain dict, ``{"device": {chip: [[name, start_ns, dur_ns], ...]}, "host":
[[name, start_ns, dur_ns], ...]}``; everything else here works on that dict,
so a recorded one (``benchmarks/tests/trace_small.json``) checks the
arithmetic without a chip.

Device events are those of the "XLA Ops" line of each ``/device:TPU:n``
plane.  A ``while`` or ``conditional`` encloses the operations of its body, so
time by operation is self time: an event's duration less what its children
cover.  Host events are the benchmark's own ``bench/...`` annotations.
"""
from __future__ import annotations

import glob
import json
import os

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench/"


def read_profile(trace_dir: str) -> dict | None:
    """The newest trace under ``trace_dir`` as a plain dict, or None where the
    profiler wrote none or it holds no accelerator plane."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events if ev.name.startswith(HOST_PREFIX)]
    if not device:
        return None
    return {"device": device, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def window_of(trace: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: the span of the benchmark's
    host annotations, or of the device events where there are none."""
    evs = trace["host"] or [e for chip in trace["device"].values() for e in chip]
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def window_seconds(trace: dict) -> float:
    lo, hi = window_of(trace)
    return (hi - lo) / 1e9


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = window_of(trace)
    per_chip = []
    for events in trace["device"].values():
        clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi]
        per_chip.append(sum(b - a for a, b in _union(clipped)) / 1e9)
    return sum(per_chip) / len(per_chip)


def self_times(events) -> dict:
    """{name: seconds} of self time: nested events (a loop and its body) are
    not counted twice."""
    out, stack = {}, []      # stack of [name, end_ns, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def op_seconds(trace: dict) -> dict:
    """{operation: self seconds}, averaged over the chips."""
    total = {}
    for events in trace["device"].values():
        for name, sec in self_times(events).items():
            total[name] = total.get(name, 0.0) + sec / len(trace["device"])
    return total


def kernel_seconds(trace: dict | None, kernel: str) -> float | None:
    """Self seconds of ``kernel``'s operations: ``kernels.json`` maps a kernel's
    name to the words its operations carry in the device trace.  None where
    there is no trace or none of them ran."""
    if trace is None:
        return None
    with open(os.path.join(os.path.dirname(__file__), "kernels.json")) as f:
        words = json.load(f)[kernel]
    return seconds_matching(trace, words)


def seconds_matching(trace: dict, words) -> float | None:
    """Self seconds of the operations whose name holds one of ``words``; None
    where none does."""
    hit = [sec for name, sec in op_seconds(trace).items()
           if any(w in name for w in words)]
    return sum(hit) if hit else None


def idle_gaps(trace: dict, top: int = 10) -> list:
    """The longest gaps of the first chip inside the window, each named by the
    host annotation that covers most of it (or "unannotated")."""
    lo, hi = window_of(trace)
    events = next(iter(trace["device"].values()))
    busy = _union([(max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "unannotated", 0
        for name, s, d in trace["host"]:
            c = min(b, s + d) - max(a, s)
            if c > cover:
                best, cover = name, c
        named.append([best, (b - a) / 1e9])
    return named


def breakdown(trace: dict, top: int = 10) -> dict:
    """The trace names an operation by its whole HLO line; 160 characters of it
    keep the name, the result's shape and the first operands."""
    ops = sorted(op_seconds(trace).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n[:160], s] for n, s in ops],
            "idle_gaps": idle_gaps(trace, top)}

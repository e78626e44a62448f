"""A device trace read one chip at a time.

``trace_reduce`` averages busy time and operation time over the chips, which
on one chip is that chip's.  Across chips a share is read from the chip that
sets the pace, the one busy longest in the window, and the chips are set
against each other, never added: a share summed over four chips would read
up to 400%.  On a one-chip trace every function here gives what
``trace_reduce`` gives.
"""
from __future__ import annotations

import json
import os

from benchmarks import phase_reduce
from benchmarks.trace_reduce import _union, self_times, window_of


def busy_by_chip(trace: dict) -> dict:
    """{chip: seconds in which an operation ran on it, inside the window}."""
    lo, hi = window_of(trace)
    out = {}
    for chip, events in trace["device"].items():
        clipped = [(max(s, lo), min(s + d, hi)) for _, s, d in events
                   if s + d > lo and s < hi]
        out[chip] = sum(b - a for a, b in _union(clipped)) / 1e9
    return out


def pacing_chip(trace: dict) -> str:
    """The chip busy longest in the window: the others wait on it."""
    busy = busy_by_chip(trace)
    return max(busy, key=busy.get)


def op_seconds(trace: dict, chip: str) -> dict:
    """{operation: self seconds} of one chip."""
    return self_times(trace["device"][chip])


def scope_seconds(trace: dict, chip: str, obs) -> dict:
    """{scope: self seconds} of one chip's operations, by the program's
    scope table (``phase_reduce.scope_seconds``)."""
    with open(os.path.join(os.path.dirname(__file__), "kernels.json")) as f:
        words = [w for ws in json.load(f).values() for w in ws]
    return phase_reduce.scope_seconds(op_seconds(trace, chip), obs.device_scopes(),
                                      obs.scopes.op_key, words)


COLLECTIVE = "lgbm/collective/"


def collective_seconds(trace: dict, chip: str, obs) -> float:
    """Self seconds of one chip's operations under ``lgbm/collective/...``:
    the exchange itself and, since a chip that comes to a collective first
    waits inside it, that chip's wait for the others."""
    return sum(sec for scope, sec in scope_seconds(trace, chip, obs).items()
               if scope.startswith(COLLECTIVE))


def names_collectives(obs) -> bool:
    """The program's scope table has the collective scopes (the parent of
    the four-chip cell has none)."""
    return obs is not None and any(s.startswith(COLLECTIVE) for s in obs.scopes.SCOPES)

"""Device phases: the ``jax.named_scope`` names the program puts on its
work, and the table that lets a device trace be read by them.

A device trace names an operation by its HLO line without metadata
(``%fusion.968 = s32[13281250]{0:T(1024)} fusion(...``), so the scope a
``jax.named_scope`` gave the operation is not in the trace.  It is in the
compiled program's text (``metadata={op_name="jit(fn)/.../lgbm/..."}``), and
:func:`record_compiled` reads it from there once per compiled program into a
process-global table of plain strings, ``{key: scope}``:

- ``key`` (:func:`op_key`) is the instruction's name and its result shape
  without layout, ``"fusion.968 s32[13281250]"`` (a tuple result is written
  ``(...)``): what tells two programs' ``%fusion.50`` apart;
- ``scope`` is the longest name of :data:`SCOPES` on the operation's
  ``op_name``, read from the last ``lgbm/`` root with the control-flow and
  transform components (``while/body``, ``cond/branch_1_fun``, ``vmap(``,
  ``jit(...)``) taken out; ``""`` for an operation under no ``lgbm/`` scope;
  :data:`AMBIGUOUS` where two recorded operations share a key and differ in
  scope.

A fusion that XLA builds across two scopes carries one of them.  The table
holds no device array and outlives every ``Booster``.
"""
from __future__ import annotations

import collections
import re
import threading
import zlib
from typing import Dict, Optional

__all__ = ["SCOPES", "AMBIGUOUS", "op_key", "scope_of", "record_compiled",
           "device_scopes", "reset_scopes"]

#: every scope the program names (the contract; PERF.md lists the metric
#: that reads each)
SCOPES = (
    "lgbm/gradients",
    "lgbm/sample",
    "lgbm/root",
    "lgbm/frontier_round",
    "lgbm/frontier_round/select",
    # the round's [N]-pass, in row order: decide = slot comparisons, bin
    # read, go-left; rank = the slot update; scatter = the one sort that
    # groups and counts the smaller children's rows (no scatter is left
    # under it: the name is the benchmark's)
    "lgbm/frontier_round/partition",
    "lgbm/frontier_round/partition/decide",
    "lgbm/frontier_round/partition/rank",
    "lgbm/frontier_round/partition/scatter",
    "lgbm/frontier_round/bookkeeping",
    "lgbm/frontier_round/hist_gather",
    "lgbm/frontier_round/hist",
    "lgbm/split_search",
    # pair subtraction of siblings and a leaf's totals from its own
    # histogram, wherever nested (both growers)
    "lgbm/sum_repair",
    "lgbm/finalize",
    "lgbm/score_update",
    "lgbm/valid_traverse",
    # the serial grower (ops/grower.py)
    "lgbm/partition",
    "lgbm/hist",
    "lgbm/apply_split",
)
AMBIGUOUS = "ambiguous"

_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# components of an op_name that are control flow or a transform, not a scope
_NOT_SCOPE = re.compile(
    r"^(while|body|cond|branch_\d+_fun|checkpoint|remat\d*|"
    r"(jit|pjit|shard_map|custom_jvp|custom_vjp)\(.*\)?)$")
_BY_LENGTH = sorted(SCOPES, key=len, reverse=True)

#: the table keeps whole programs, the newest first to stay, up to this many
#: operations (a process that compiles without end must not grow without end)
MAX_OPS = 200_000

_lock = threading.Lock()
# text checksum -> {key: scope} of one program, oldest first
_programs: "collections.OrderedDict[int, Dict[str, str]]" = \
    collections.OrderedDict()


def op_key(line: str) -> Optional[str]:
    """The key of an operation from its HLO line (a compiled program's text
    or a device trace's event name): ``"<name> <result shape>"``."""
    m = _LINE.match(line)
    if m is None:
        return None
    name, rest = m.groups()
    if rest.startswith("("):
        return name + " (...)"
    return name + " " + _LAYOUT.sub("", rest.split(" ", 1)[0])


def scope_of(op_name: str) -> str:
    """The scope of :data:`SCOPES` an ``op_name`` lies under, or ``""``."""
    at = op_name.rfind("lgbm/")
    if at < 0:
        return ""
    parts = [p for p in op_name[at:].replace("vmap(", "").replace(")", "")
             .split("/") if not _NOT_SCOPE.match(p)]
    path = "/".join(parts) + "/"
    for scope in _BY_LENGTH:
        if path.startswith(scope + "/"):
            return scope
    return "/".join(parts[:2])


def record_compiled(compiled) -> int:
    """Read one compiled program's optimized HLO into the table; returns the
    number of operations read.  ``compiled`` is a ``jax.stages.Compiled``;
    nothing of it is kept.  The instructions inside a fusion never show in a
    trace (the fusion does) and are left out."""
    text = compiled.as_text()
    rows: Dict[str, str] = {}
    fused = False
    for line in text.splitlines():
        if line.endswith("{") and "->" in line:     # a computation's header
            fused = line.lstrip("%").startswith("fused_computation")
            continue
        if fused or " = " not in line:
            continue
        key = op_key(line)
        if key is None:
            continue
        m = _OP_NAME.search(line)
        rows[key] = scope_of(m.group(1)) if m else ""
    with _lock:
        _programs[zlib.crc32(text.encode())] = rows
        while (len(_programs) > 1
               and sum(map(len, _programs.values())) > MAX_OPS):
            _programs.popitem(last=False)
    return len(rows)


def device_scopes() -> Dict[str, str]:
    """``{key: scope}`` over the operations of every program recorded in this
    process (plain strings, a fresh dict)."""
    table: Dict[str, str] = {}
    with _lock:
        for rows in _programs.values():
            for key, scope in rows.items():
                if table.setdefault(key, scope) != scope:
                    table[key] = AMBIGUOUS
    return table


def reset_scopes() -> None:
    with _lock:
        _programs.clear()

"""XLA cost ledger + roofline/MFU accounting (device-truth attribution).

One audited peak table and one cost model for the package: every MFU or
peak-rate figure the package prints (obs-report, the serve and training
cost events) comes through here, and ``tests/test_obs.py`` greps the tree
to enforce it (the benchmark keeps its own, ``benchmarks/peaks.json``).
XLA's own compiled-program cost model is the source of truth and the
analytic work models are labelled predictions.

Stdlib-only at import: jax is imported lazily inside the few functions
that touch a device, and the :class:`CostLedger` duck-types the
``Compiled`` objects callers hand it.

Two layers:

- **peaks + math** — :data:`PEAK_RATES` (bf16 FLOP/s + HBM B/s per chip
  kind), :func:`peak_flops`, :func:`peak_bandwidth`, :func:`mfu`,
  :func:`arithmetic_intensity`, :func:`ridge_intensity`,
  :func:`roofline` (the full achieved-vs-peak record with the
  compute-vs-bandwidth-bound classification);
- **ledger** — :class:`CostLedger` wraps named jit/lowered programs,
  records ``Compiled.cost_analysis()`` (flops, bytes accessed,
  transcendentals) and ``memory_analysis()`` (argument/output/temp
  bytes; peak is derived), joins them
  with measured wall times, and emits one ``program_cost`` schema event
  per program through the existing :class:`~.events.EventLog` for
  ``obs-report --roofline`` to render.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

__all__ = ["PEAK_RATES", "normalize_chip", "peak_flops",
           "peak_bandwidth", "mfu", "arithmetic_intensity",
           "ridge_intensity", "classify_bound", "roofline", "CostLedger",
           "get_ledger", "reset_ledger", "current_chip", "analyze_jitted",
           "record_watermarks", "set_stats_provider", "COST_EVENT"]

#: event name the ledger emits per program (rendered by --roofline)
COST_EVENT = "program_cost"

# --------------------------------------------------------------------------
# THE peak table.  Published per-chip dense-bf16 matmul peak and HBM
# bandwidth; keys are lowercased ``device.device_kind`` values ("tpu v5
# lite" is what a v5e chip reports).  A device that is not in the table is
# an error, not a default: a rate priced against the wrong peak is a wrong
# number under a device metric's name, so there is no CPU row either.
# --------------------------------------------------------------------------
PEAK_RATES: Dict[str, Dict[str, float]] = {
    "tpu v4":      {"flops": 275e12, "bytes_per_sec": 1228e9},
    "tpu v5e":     {"flops": 197e12, "bytes_per_sec": 819e9},
    "tpu v5 lite": {"flops": 197e12, "bytes_per_sec": 819e9},
    "tpu v5p":     {"flops": 459e12, "bytes_per_sec": 2765e9},
    "tpu v6e":     {"flops": 918e12, "bytes_per_sec": 1640e9},
    "tpu v6 lite": {"flops": 918e12, "bytes_per_sec": 1640e9},
}


def normalize_chip(kind: Optional[str]) -> str:
    """Map a ``device_kind`` string onto its peak-table key; raises
    ``KeyError`` for a kind the table does not hold."""
    k = (kind or "").strip().lower()
    if k not in PEAK_RATES:
        raise KeyError(
            f"device kind {kind!r} has no row in obs.costs.PEAK_RATES "
            f"(known: {', '.join(PEAK_RATES)}); add its published peaks "
            "before pricing a run on it")
    return k


def peak_flops(kind: Optional[str]) -> float:
    return PEAK_RATES[normalize_chip(kind)]["flops"]


def peak_bandwidth(kind: Optional[str]) -> float:
    return PEAK_RATES[normalize_chip(kind)]["bytes_per_sec"]


def mfu(flops: float, seconds: float, kind: Optional[str]) -> float:
    """Model FLOPs Utilization: achieved FLOP/s over the chip's peak."""
    if seconds <= 0.0:
        return 0.0
    return flops / seconds / peak_flops(kind)


def arithmetic_intensity(flops: float, bytes_accessed: float) -> float:
    """FLOPs per byte moved (the roofline x-axis)."""
    return flops / bytes_accessed if bytes_accessed > 0 else float("inf")


def ridge_intensity(kind: Optional[str]) -> float:
    """The roofline ridge point: intensities above it are compute-bound."""
    return peak_flops(kind) / peak_bandwidth(kind)


def classify_bound(intensity: float, kind: Optional[str]) -> str:
    return ("compute" if intensity >= ridge_intensity(kind)
            else "bandwidth")


def roofline(flops: float, bytes_accessed: float, seconds: float,
             kind: Optional[str]) -> Dict[str, Any]:
    """Full achieved-vs-peak record for one timed program execution."""
    chip = normalize_chip(kind)
    ach_f = flops / seconds if seconds > 0 else 0.0
    ach_b = bytes_accessed / seconds if seconds > 0 else 0.0
    ai = arithmetic_intensity(flops, bytes_accessed)
    return {
        "chip": chip,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "seconds": seconds,
        "achieved_flops_per_sec": ach_f,
        "achieved_bytes_per_sec": ach_b,
        "mfu": ach_f / peak_flops(chip),
        "hbm_util": ach_b / peak_bandwidth(chip),
        "intensity": ai,
        "ridge_intensity": ridge_intensity(chip),
        "bound": classify_bound(ai, chip),
    }


# --------------------------------------------------------------------------
# device access (jax imported lazily: the module itself stays stdlib-only)
# --------------------------------------------------------------------------

def _device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind.strip().lower()


def current_chip() -> str:
    """Peak-table key of the default device; raises ``KeyError`` when the
    table does not hold it (the CPU backend included)."""
    return normalize_chip(_device_kind())


#: test seam for :func:`record_watermarks` — ``device.memory_stats()`` is
#: None on CPU, so CPU-only tests inject a fake provider here
_STATS_PROVIDER: Optional[Callable[[], Optional[Dict[str, Any]]]] = None


def set_stats_provider(
        fn: Optional[Callable[[], Optional[Dict[str, Any]]]]) -> None:
    global _STATS_PROVIDER
    _STATS_PROVIDER = fn


def _device_memory_stats() -> Optional[Dict[str, Any]]:
    if _STATS_PROVIDER is not None:
        return _STATS_PROVIDER()
    try:
        import jax
        return jax.devices()[0].memory_stats()
    except Exception:
        return None


def record_watermarks(prefix: str, registry: Any = None) -> Dict[str, int]:
    """Mirror ``device.memory_stats()`` watermarks into the metrics
    registry as ``<prefix>.device_bytes_in_use`` (last value) and
    ``<prefix>.device_peak_bytes_in_use`` (monotone max).  A local C++
    call, no device sync; returns ``{}`` where the backend publishes no
    stats (CPU) so call sites never need to branch."""
    stats = _device_memory_stats()
    if not stats:
        return {}
    if registry is None:
        from .metrics import get_registry
        registry = get_registry()
    out: Dict[str, int] = {}
    if "bytes_in_use" in stats:
        v = int(stats["bytes_in_use"])
        registry.gauge(f"{prefix}.device_bytes_in_use").set(v)
        out["bytes_in_use"] = v
    if "peak_bytes_in_use" in stats:
        v = int(stats["peak_bytes_in_use"])
        registry.gauge(f"{prefix}.device_peak_bytes_in_use").set_max(v)
        out["peak_bytes_in_use"] = v
    return out


# --------------------------------------------------------------------------
# the ledger
# --------------------------------------------------------------------------

def _cost_dict(compiled: Any) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` (a plain dict; None on backends without
    an analysis) reduced to the keys of interest: ``flops``,
    ``bytes accessed``, ``transcendentals``."""
    ca = compiled.cost_analysis() or {}
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool) and v >= 0:
            out[name] = float(v)
    return out


def _memory_dict(compiled: Any) -> Dict[str, int]:
    """``Compiled.memory_analysis()`` normalized.  ``CompiledMemoryStats``
    has argument/output/temp/alias sizes; ``peak_bytes`` is derived as
    arg+out+temp-alias (what the executable pins at once, the planning
    number OOM math needs)."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for attr, name in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes", "code_bytes")):
        v = getattr(ma, attr, None)
        if isinstance(v, int) and v >= 0:
            out[name] = v
    if {"argument_bytes", "output_bytes", "temp_bytes"} <= out.keys():
        out["peak_bytes"] = (out["argument_bytes"] + out["output_bytes"]
                             + out["temp_bytes"] - out.get("alias_bytes", 0))
    return out


class CostLedger:
    """Named-program registry of XLA cost/memory analysis joined with
    measured wall time.

    ``record(name, compiled, **meta)`` captures the compiler's view once
    (at compile time — free); ``observe(name, seconds)`` accumulates
    measured executions; ``rooflines()`` joins the two against the peak
    table; ``emit(log)`` appends one ``program_cost`` schema event per
    program for ``obs-report --roofline``.
    """

    def __init__(self, chip: Optional[str] = None):
        self._chip = chip
        self._lock = threading.Lock()
        self._programs: Dict[str, Dict[str, Any]] = {}

    def __contains__(self, name: str) -> bool:
        return name in self._programs

    def names(self) -> List[str]:
        return list(self._programs)

    def entry(self, name: str) -> Dict[str, Any]:
        return dict(self._programs[name])

    # ------------------------------------------------------------------
    def record(self, name: str, compiled: Any = None, *,
               chip: Optional[str] = None, model_flops: Optional[float] = None,
               **meta: Any) -> Dict:
        """Register/refresh a program.  ``compiled`` is any object with
        ``cost_analysis``/``memory_analysis`` (jax ``Compiled``); pass
        ``model_flops`` for an analytic work model to report alongside
        XLA's count."""
        # the device kind is stored as found; pricing it against the peak
        # table (rooflines) is where an unknown kind raises
        ent: Dict[str, Any] = {"program": name,
                               "chip": chip or self._chip or _device_kind()}
        if compiled is not None:
            ent["cost"] = _cost_dict(compiled)
            ent["memory"] = _memory_dict(compiled)
        if model_flops is not None:
            ent["model_flops"] = float(model_flops)
        if meta:
            ent["meta"] = {k: v for k, v in meta.items()}
        with self._lock:
            prev = self._programs.get(name, {})
            ent.setdefault("calls", prev.get("calls", 0))
            ent.setdefault("total_seconds", prev.get("total_seconds", 0.0))
            self._programs[name] = ent
        return ent

    def observe(self, name: str, seconds: float, calls: int = 1) -> None:
        """Join ``calls`` measured executions totalling ``seconds`` with
        the program's recorded analysis (no-op for unknown names so call
        sites need no existence branch)."""
        if seconds is None or seconds < 0:
            return
        with self._lock:
            ent = self._programs.get(name)
            if ent is None:
                return
            ent["calls"] = ent.get("calls", 0) + int(calls)
            ent["total_seconds"] = ent.get("total_seconds", 0.0) + float(seconds)

    # ------------------------------------------------------------------
    def rooflines(self) -> List[Dict[str, Any]]:
        """One achieved-vs-peak record per OBSERVED program (programs with
        analysis but no timings are skipped: no wall time, no rate).
        Raises ``KeyError`` when a program ran on a device kind the peak
        table does not hold."""
        out = []
        with self._lock:
            entries = [dict(e) for e in self._programs.values()]
        for ent in entries:
            calls = ent.get("calls", 0)
            secs = ent.get("total_seconds", 0.0)
            if not calls or secs <= 0:
                continue
            cost = ent.get("cost", {})
            flops = cost.get("flops", ent.get("model_flops", 0.0)) * calls
            byts = cost.get("bytes_accessed", 0.0) * calls
            rec = roofline(flops, byts, secs, ent["chip"])
            rec.update(program=ent["program"], calls=calls,
                       seconds_per_call=secs / calls,
                       flops_source=("xla" if "flops" in cost else "model"))
            for k in ("model_flops", "memory", "meta"):
                if k in ent:
                    rec[k] = ent[k]
            if "model_flops" in ent:
                rec["model_mfu"] = mfu(ent["model_flops"] * calls, secs,
                                       ent["chip"])
            out.append(rec)
        return out

    def emit(self, log: Any = None, event: str = COST_EVENT) -> int:
        """Append one schema event per observed program; returns the
        count.  ``log`` defaults to the shared journal writer."""
        if log is None:
            from .events import EventLog
            log = EventLog.default()
        rows = self.rooflines()
        for rec in rows:
            log.emit(event, **_round_floats(rec))
        return len(rows)


def _round_floats(obj: Any, nd: int = 6) -> Any:
    if isinstance(obj, float):
        return round(obj, nd) if obj == obj and abs(obj) != float("inf") \
            else str(obj)
    if isinstance(obj, dict):
        return {k: _round_floats(v, nd) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_round_floats(v, nd) for v in obj]
    return obj


_LEDGER = CostLedger()


def get_ledger() -> CostLedger:
    """The process-wide ledger (mirrors the metrics-registry pattern)."""
    return _LEDGER


def reset_ledger() -> CostLedger:
    global _LEDGER
    _LEDGER = CostLedger()
    return _LEDGER


def analyze_jitted(name: str, fn: Callable, *args: Any,
                   ledger: Optional[CostLedger] = None,
                   **record_kw: Any) -> Dict[str, Any]:
    """Lower+compile ``fn`` AOT on ``args``, record its analysis under
    ``name`` and read its operations' scopes into the device-scope table
    (``obs.scopes``).  For an ``fn`` that was just called on the same
    arguments jax hands back the trace and the executable it holds: no
    second compilation.  Returns the ledger entry."""
    import jax
    from . import scopes
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args).compile()
    scopes.record_compiled(compiled)
    return (ledger or get_ledger()).record(name, compiled, **record_kw)

"""Unified telemetry: structured events, metrics, tracing, reporting.

The observability subsystem (docs/OBSERVABILITY.md).  Its layers, all
stdlib-only at import:

- :mod:`.events` — versioned structured-event schema + the thread-safe
  jsonl :class:`~.events.EventLog` behind ``perf_results.jsonl``;
- :mod:`.metrics` — process-wide counters/gauges/reservoir-percentile
  histograms, snapshottable on demand;
- :mod:`.tracer` — nested, thread-safe host spans, always recorded in a
  bounded ring on the profiler's clock, each also a ``jax.profiler``
  annotation; compilations become ``lgbm/compile`` spans;
- :mod:`.scopes` — the ``jax.named_scope`` names of the device phases and
  :func:`device_scopes`, the table that reads a device trace by them;
- :mod:`.report` — the ``python -m lightgbm_tpu obs-report`` renderer.

:class:`TrainTelemetry` is the glue the boosting loops hold: one object
wiring config knobs (``obs_telemetry``, ``obs_events_path``) to an event
log, the metrics registry and the global tracer.
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from . import costs, flight, health, scopes
from .costs import CostLedger, get_ledger
from .events import (EventLog, SCHEMA_VERSION, classify_record, make_event,
                     new_run_id, perf_log_path, validate_event)
from .flight import FlightRecorder
from .health import DivergenceError, SLOMonitor
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry)
from .scopes import device_scopes
from .tracer import Span, Tracer, get_tracer, install_compile_listener

__all__ = ["EventLog", "SCHEMA_VERSION", "classify_record", "make_event",
           "new_run_id", "perf_log_path", "validate_event",
           "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry", "Span", "Tracer", "get_tracer",
           "install_compile_listener", "device_scopes",
           "costs", "scopes", "CostLedger", "get_ledger",
           "flight", "health", "FlightRecorder", "DivergenceError",
           "SLOMonitor", "TrainTelemetry"]


class TrainTelemetry:
    """Per-booster telemetry hook (constructed when ``obs_telemetry`` is
    on; the boosting loop holds ``None`` otherwise, so the off path costs
    one attribute check per iteration).

    Wires the config to the subsystem: events go to ``obs_events_path``
    (default: the shared perf journal) and per-iteration span seconds feed
    named histograms in the process registry.  The spans themselves need no
    telemetry: the global tracer records them always.
    """

    def __init__(self, config: Any, kind: str = "train"):
        self.kind = kind
        path = getattr(config, "obs_events_path", "") or None
        self.log = EventLog(path) if path else EventLog.default()
        self.run_id = self.log.run_id
        self.metrics = get_registry()
        self.reservoir = int(getattr(config, "obs_reservoir_size", 512))
        self.tracer = get_tracer()
        # health plane: arm the flight recorder (dump lands beside the
        # journal unless LGBM_FLIGHT_DIR redirects it), publish the run
        # on the status board, start the exposition server when enabled
        flight.install(dir=os.path.dirname(os.path.abspath(self.log.path)),
                       run_id=self.run_id)
        health.set_status(run_id=self.run_id, stage=self.kind)
        health.maybe_start(getattr(config, "obs_health_port", 0))

    # ------------------------------------------------------------------
    def span_seconds(self, it: int) -> Dict[str, float]:
        """Seconds of the ``lgbm/update`` span of iteration ``it`` and of its
        children, by span name.  Host seconds: what the host spent issuing
        (``grow_dispatch``, ``score_dispatch``) and waiting
        (``drain``), not what the device spent on a phase."""
        out: Dict[str, float] = {}
        for s in reversed(self.tracer.spans()):
            if s.iteration is not None and s.iteration < it:
                break
            if s.iteration == it and s.name.startswith("lgbm/update"):
                out[s.name] = round(out.get(s.name, 0.0) + s.duration, 6)
        return out

    def iteration_event(self, it: int, *, trees: int,
                        extra: Optional[Dict[str, Any]] = None) -> None:
        """Emit the per-iteration training event + update metrics."""
        spans = self.span_seconds(it)
        self.metrics.counter(f"{self.kind}.iterations").inc()
        for name, secs in spans.items():
            # lgbm/update/grow_dispatch -> train.grow_dispatch_seconds
            self.metrics.histogram(
                f"{self.kind}.{name.rsplit('/', 1)[-1]}_seconds",
                self.reservoir).observe(secs)
        rec: Dict[str, Any] = {"iteration": it, "trees": trees,
                               "span_seconds": spans}
        # device-memory watermarks (local stats read, no device sync; CPU
        # publishes none and the helper degrades to {}) + the cost-ledger
        # wall-time join for the recorded grow program
        wm = costs.record_watermarks(self.kind, self.metrics)
        if wm:
            rec["device_memory"] = wm
        if extra:
            rec.update(extra)
        self.log.emit(f"{self.kind}_iter", **rec)
        health.set_status(stage=self.kind, iteration=it)
        # surface the tracer's silent data loss once per overflow episode
        if self.tracer.dropped and not self.tracer.overflow_reported:
            self.tracer.overflow_reported = True
            self.log.emit("tracer_overflow", level="warning",
                          dropped=self.tracer.dropped,
                          capacity=self.tracer.capacity)

    def tree_event(self, it: int, *, num_leaves: int,
                   split_gains: Optional[List[float]] = None) -> None:
        """Per-materialized-tree stats: leaves + split-gain summary.  On
        the fast path this fires from ``_drain_pending`` (the existing
        host materialization point) so telemetry never forces an extra
        device sync."""
        self.metrics.histogram(f"{self.kind}.num_leaves",
                               self.reservoir).observe(num_leaves)
        rec: Dict[str, Any] = {"iteration": it, "num_leaves": num_leaves}
        if split_gains:
            gains = [float(g) for g in split_gains]
            rec["split_gain"] = {
                "splits": len(gains),
                "max": round(max(gains), 6),
                "mean": round(sum(gains) / len(gains), 6),
                "total": round(sum(gains), 6)}
            self.metrics.histogram(f"{self.kind}.split_gain",
                                   self.reservoir).observe(max(gains))
        self.log.emit(f"{self.kind}_tree", **rec)

"""Host spans: nested, thread-safe, always on, on the profiler's clock.

The process-global :class:`Tracer` keeps the last ``capacity`` completed
spans in memory, whatever the configuration.  What keeps that free is the
rule for span sites: **a site fires O(1) times per ``Dataset.construct``,
``Booster.__init__``, ``update()``, ``eval_*()`` or compilation; never per
split, per block, per row or per request.**  A tree of many seconds is about
a dozen ring appends.

- A :class:`Span` records ``id``, ``parent`` (the id of the span that was
  open on the same thread when it began, or None), ``name``, ``start`` and
  ``end`` in ``time.time_ns()`` nanoseconds (the clock ``jax.profiler`` stamps
  its host events with, so spans and a device trace share one time axis),
  ``tid``, and the boosting ``iteration`` it belongs to (inherited from the
  parent when not given; the identifier the spans of one tree share).
- Every span also enters a ``jax.profiler.TraceAnnotation`` when jax is
  loaded: a flag test while no capture runs, and with a capture running (an
  operator's own ``jax.profiler.start_trace``) the spans show beside the
  device operations with no knob to turn.
- The ring drops the OLDEST span when full and counts it in ``dropped``.
- :func:`install_compile_listener` registers one ``jax.monitoring`` listener
  that turns every compilation (or cache load) into an ``lgbm/compile`` span
  parented to whatever was open, and counts ``compile.count``,
  ``compile.cache_hits`` and ``compile.cache_misses``.

The module itself is stdlib-only; jax is touched only if something else has
imported it already (a supervisor that loads ``obs`` jax-free stays so).
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["Span", "Tracer", "get_tracer", "install_compile_listener"]


class Span:
    """One completed scope."""

    __slots__ = ("id", "parent", "name", "start", "end", "tid", "depth",
                 "iteration", "args")

    def __init__(self, id: int, parent: Optional[int], name: str, start: int,
                 end: int, tid: int, depth: int, iteration: Optional[int],
                 args: Optional[Dict[str, Any]]):
        self.id = id
        self.parent = parent
        self.name = name
        self.start = start          # time.time_ns()
        self.end = end
        self.tid = tid
        self.depth = depth
        self.iteration = iteration
        self.args = args

    @property
    def duration(self) -> float:
        """Seconds."""
        return (self.end - self.start) / 1e9

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__}


class _OpenSpan:
    __slots__ = ("id", "name", "start", "iteration", "args", "annotation")

    def __init__(self, id, name, start, iteration, args, annotation):
        self.id = id
        self.name = name
        self.start = start
        self.iteration = iteration
        self.args = args
        self.annotation = annotation    # entered jax TraceAnnotation or None


def _annotate(name: str):
    """Enter a profiler annotation if jax is loaded (never imports it)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann
    except Exception:
        return None     # no profiler: the span is still recorded


class Tracer:
    """Thread-safe span recorder over a bounded ring: the oldest spans go
    first and ``dropped`` counts them (a tracer must never become the leak
    it is measuring)."""

    def __init__(self, capacity: int = 16_384):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._spans: "collections.deque[Span]" = collections.deque()
        self._ids = itertools.count(1)
        self.dropped = 0
        #: set by the first ``tracer_overflow`` warning event so the
        #: warning fires once per overflow episode, not per iteration
        self.overflow_reported = False
        self._local = threading.local()
        # tid -> that thread's open-span stack; thread-locals are not
        # enumerable from another thread, and the flight recorder needs
        # the open spans of EVERY thread at crash time
        self._stacks: Dict[int, List[_OpenSpan]] = {}

    # ------------------------------------------------------------------
    def _stack(self) -> List[_OpenSpan]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
            with self._lock:
                self._stacks[threading.get_ident()] = st
        return st

    def open_spans(self) -> List[Dict[str, Any]]:
        """Snapshot of every thread's currently-open spans (crash
        forensics: what was in flight when the process died)."""
        now = time.time_ns()
        out: List[Dict[str, Any]] = []
        with self._lock:
            stacks = {tid: list(st) for tid, st in self._stacks.items()}
        for tid, stack in sorted(stacks.items()):
            for depth, o in enumerate(stack):
                out.append({"name": o.name, "tid": tid, "depth": depth,
                            "age_s": round((now - o.start) / 1e9, 6),
                            "args": o.args})
        return out

    def _keep(self, span: Span) -> None:
        with self._lock:
            while self._spans and len(self._spans) >= self.capacity:
                self._spans.popleft()       # the oldest goes
                self.dropped += 1
            if self.capacity > 0:
                self._spans.append(span)
            else:
                self.dropped += 1

    def begin(self, name: str, iteration: Optional[int] = None,
              **args: Any) -> None:
        """Open a span on the calling thread (pairs with :meth:`end`)."""
        stack = self._stack()
        if iteration is None and stack:
            iteration = stack[-1].iteration
        stack.append(_OpenSpan(next(self._ids), name, time.time_ns(),
                               iteration, args or None, _annotate(name)))

    def end(self, name: str, **args: Any) -> None:
        """Close the innermost open span named ``name`` on this thread;
        ``args`` (what the scope found out) join those given at its begin.
        Unbalanced ends are ignored (a tracer must not crash its host)."""
        now = time.time_ns()
        stack = self._stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i].name == name:
                o = stack.pop(i)
                break
        else:
            return
        if o.annotation is not None:
            try:
                o.annotation.__exit__(None, None, None)
            except Exception:
                pass
        if args:
            o.args = {**(o.args or {}), **args}
        self._keep(Span(o.id, stack[i - 1].id if i else None, name, o.start,
                        now, threading.get_ident(), i, o.iteration, o.args))

    @contextlib.contextmanager
    def span(self, name: str, iteration: Optional[int] = None, **args: Any):
        self.begin(name, iteration, **args)
        try:
            yield
        finally:
            self.end(name)

    def record(self, name: str, start: int, end: int, **args: Any) -> None:
        """Keep a span that already happened (a duration reported after the
        fact, as ``jax.monitoring`` does), parented to the innermost span
        open on the calling thread."""
        stack = self._stack()
        top = stack[-1] if stack else None
        self._keep(Span(next(self._ids), top.id if top else None, name,
                        int(start), int(end), threading.get_ident(),
                        len(stack), top.iteration if top else None,
                        args or None))

    # ------------------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def aggregate(self) -> Dict[str, Dict[str, Any]]:
        """Per-name totals: ``{name: {"count", "total_s"}}``."""
        out: Dict[str, Dict[str, Any]] = {}
        for s in self.spans():
            agg = out.setdefault(s.name, {"count": 0, "total_s": 0.0})
            agg["count"] += 1
            agg["total_s"] += s.duration
        return out

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0
            self.overflow_reported = False


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer: records always, with no parameter."""
    return _TRACER


# --------------------------------------------------------------------------
# compilations as spans
# --------------------------------------------------------------------------
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": True,
                 "/jax/compilation_cache/cache_misses": False}
_listener_lock = threading.Lock()
_listener_installed = False
_cache_seen = threading.local()


def _on_event(name: str, **kw: Any) -> None:
    hit = _CACHE_EVENTS.get(name)
    if hit is not None:
        # fired inside the compile the duration listener reports next, on
        # the same thread
        _cache_seen.hit = hit


def _on_duration(name: str, secs: float, **kw: Any) -> None:
    if name != _COMPILE_EVENT:
        return
    from .metrics import counter
    end = time.time_ns()
    hit = getattr(_cache_seen, "hit", None)
    _cache_seen.hit = None
    counter("compile.count").inc()
    if hit is not None:
        counter("compile.cache_hits" if hit else "compile.cache_misses").inc()
    _TRACER.record("lgbm/compile", end - int(secs * 1e9), end,
                   fun=str(kw.get("fun_name", "")), seconds=float(secs),
                   cache_hit=hit)


def install_compile_listener() -> bool:
    """Register the one ``jax.monitoring`` listener (idempotent).  ``seconds``
    is what jax times as the backend compile: a compilation, or the load of
    a persistent-cache entry (``cache_hit`` true; None where no persistent
    cache is in use)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return True
        try:
            from jax import monitoring
        except Exception:
            return False
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _listener_installed = True
        return True

"""Structured telemetry core: spans, instants, counters, gauges.

Design constraints (see docs/observability.md):

- **Zero-cost when disabled.**  ``span()`` on a disabled ``Telemetry``
  returns a module-level ``_NULL_SPAN`` singleton — no allocation, no
  clock read, no lock.  The trainer hot loop and the serving decode path
  keep their instrumentation unconditionally; turning telemetry off is a
  single flag, not an edit.
- **Thread-safe.**  The checkpoint manager emits ``ckpt.save`` spans from
  its async writer thread while the trainer emits ``train.step`` spans
  from the main thread.  Sink emission and counter accumulation are
  lock-protected; the span *stack* (for nesting depth / parent
  attribution) is thread-local, so concurrent spans never see each other
  as parents.
- **On the profiler's clock.**  A live span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name with its constructor
  attributes, so a ``jax.profiler`` trace shows the program's host spans
  beside the device ops (``step`` and ``what`` become event stats).
- **Events are plain dicts** (JSON-ready), one schema for every sink:

      {"name": str, "kind": "span"|"instant"|"counter"|"gauge",
       "ts": float seconds since the Telemetry epoch,
       "dur": float seconds (spans only),
       "tid": int python thread id, "depth": int, "parent": str|None,
       "value"/"total": numbers (counter/gauge),
       "attrs": {str: json-able}}

The module-level ``span``/``instant``/``counter``/``gauge`` helpers
delegate to a process-global ``Telemetry`` (disabled by default) that
``configure()`` swaps in — library code instruments against the
module API and launch scripts decide whether anything is recorded.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Telemetry",
    "configure",
    "counter",
    "gauge",
    "get_telemetry",
    "instant",
    "set_telemetry",
    "span",
]


class _NullSpan:
    """Do-nothing span handed out when telemetry is disabled.

    A single module-level instance (``_NULL_SPAN``) is reused for every
    disabled ``span()`` call so the disabled path allocates nothing.
    """

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """Live span: records wall time between ``__enter__`` and ``__exit__``
    and emits one ``kind="span"`` event on exit (including on exception,
    in which case the event carries an ``error`` attr and the exception
    propagates).  While open it also holds a profiler ``TraceAnnotation``
    of the same name and constructor attributes."""

    __slots__ = ("_tel", "name", "attrs", "t0", "depth", "parent", "_ann")

    def __init__(self, tel: "Telemetry", name: str, attrs: Dict[str, Any]):
        self._tel = tel
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.depth = 0
        self.parent: Optional[str] = None
        self._ann = TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> "_Span":
        """Merge attrs into the span mid-flight (e.g. byte counts known
        only after the work ran)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "_Span":
        stack = self._tel._stack()
        self.depth = len(stack)
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        stack = self._tel._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        self._tel._emit(
            {
                "name": self.name,
                "kind": "span",
                "ts": self.t0 - self._tel.epoch,
                "dur": t1 - self.t0,
                "tid": threading.get_ident(),
                "depth": self.depth,
                "parent": self.parent,
                "attrs": self.attrs,
            }
        )
        return False


class Telemetry:
    """Event router: validates nothing, timestamps everything, fans events
    out to ``sinks`` under a lock.  Counters additionally accumulate
    in-process so totals survive even with no sink attached."""

    def __init__(self, enabled: bool = True, sinks: Optional[List] = None):
        self.enabled = enabled
        self.sinks = list(sinks) if sinks else []
        self.epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._local = threading.local()
        self.counters: Dict[str, float] = {}

    # -- internals ---------------------------------------------------------

    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = []
            self._local.stack = stack
        return stack

    def _emit(self, event: Dict[str, Any]) -> None:
        with self._lock:
            for sink in self.sinks:
                sink.emit(event)

    # -- API ---------------------------------------------------------------

    def span(self, name: str, **attrs):
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "kind": "instant",
                "ts": time.perf_counter() - self.epoch,
                "tid": threading.get_ident(),
                "depth": len(self._stack()),
                "parent": self._stack()[-1].name if self._stack() else None,
                "attrs": attrs,
            }
        )

    def record_span(self, name: str, dur_s: float, **attrs) -> None:
        """Emit a span event with an externally-measured duration (e.g. a
        min-of-N microbench result) — the timed region itself stays
        unobserved; the event's ts marks when it was recorded."""
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "kind": "span",
                "ts": time.perf_counter() - self.epoch,
                "dur": float(dur_s),
                "tid": threading.get_ident(),
                "depth": len(self._stack()),
                "parent": self._stack()[-1].name if self._stack() else None,
                "attrs": attrs,
            }
        )

    def counter(self, name: str, inc: float = 1.0, **attrs) -> None:
        if not self.enabled:
            return
        with self._lock:
            total = self.counters.get(name, 0.0) + inc
            self.counters[name] = total
        self._emit(
            {
                "name": name,
                "kind": "counter",
                "ts": time.perf_counter() - self.epoch,
                "tid": threading.get_ident(),
                "value": inc,
                "total": total,
                "attrs": attrs,
            }
        )

    def gauge(self, name: str, value: float, **attrs) -> None:
        if not self.enabled:
            return
        self._emit(
            {
                "name": name,
                "kind": "gauge",
                "ts": time.perf_counter() - self.epoch,
                "tid": threading.get_ident(),
                "value": float(value),
                "attrs": attrs,
            }
        )

    def close(self) -> None:
        with self._lock:
            for sink in self.sinks:
                sink.close()


# -- process-global telemetry (disabled by default) ------------------------

_GLOBAL = Telemetry(enabled=False)


def get_telemetry() -> Telemetry:
    return _GLOBAL


def set_telemetry(tel: Telemetry) -> Telemetry:
    """Swap the process-global telemetry; returns the previous one so
    tests can restore it."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = tel
    return prev


def configure(enabled: bool = True, sinks: Optional[List] = None) -> Telemetry:
    """Build + install a fresh global ``Telemetry``.  Launch scripts call
    this once (e.g. when ``--metrics-out`` is given); everything
    instrumented against the module-level helpers starts recording."""
    return_new = Telemetry(enabled=enabled, sinks=sinks)
    set_telemetry(return_new)
    return return_new


def span(name: str, **attrs):
    return _GLOBAL.span(name, **attrs)


def instant(name: str, **attrs) -> None:
    _GLOBAL.instant(name, **attrs)


def counter(name: str, inc: float = 1.0, **attrs) -> None:
    _GLOBAL.counter(name, inc, **attrs)


def gauge(name: str, value: float, **attrs) -> None:
    _GLOBAL.gauge(name, value, **attrs)

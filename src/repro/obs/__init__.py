"""Telemetry subsystem: structured spans/metrics, pluggable sinks, Chrome
trace_event export, and model-vs-measured drift tracking.

Instrument with the module-level helpers (no-ops until a launch script
calls ``obs.configure(...)``; a live span also marks a ``jax.profiler``
trace under its name):

    from repro import obs

    with obs.span("train.step", step=i) as sp:
        ...
        sp.set(loss=loss)
    obs.counter("train.host_fetches")
    obs.gauge("engine.running", len(running))

See docs/observability.md.
"""

from repro.obs.core import (
    Telemetry,
    configure,
    counter,
    gauge,
    get_telemetry,
    instant,
    set_telemetry,
    span,
)
from repro.obs.sinks import JsonlSink, RingBufferSink, Sink
from repro.obs.chrome import (
    chrome_trace,
    schedule_lane_events,
    validate_chrome_trace,
    write_chrome_trace,
)
from repro.obs.drift import SPAN_PHASES, DriftTracker

__all__ = [
    "DriftTracker",
    "JsonlSink",
    "RingBufferSink",
    "SPAN_PHASES",
    "Sink",
    "Telemetry",
    "chrome_trace",
    "configure",
    "counter",
    "gauge",
    "get_telemetry",
    "instant",
    "schedule_lane_events",
    "set_telemetry",
    "span",
    "validate_chrome_trace",
    "write_chrome_trace",
]

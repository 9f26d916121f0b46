"""Structured observability: metrics registry, spans, jit-safe taps and a
JSONL flight recorder (DESIGN.md §3.10).

Quickstart::

    from repro import obs

    with obs.recording("run.jsonl"):
        serve_loop.run(...)            # instrumented hot paths tap/record
    print(obs.summary())               # p50/p95/p99 per span, counter totals

Disabled (the default) pays zero overhead: taps are statically compiled
out, spans only enter their ``repro.<name>`` profiler annotation, and the
compile counter (:func:`compiles`, always on) runs only when something
compiles.  Jitted consumers thread
``obs_tap=obs.enabled()`` as a static argument and pin their trace with
:func:`tap_scope`, so enablement rides jit cache keys exactly like
``spmv_backend``."""
from .registry import (
    DEFAULT_BUCKETS,
    Histogram,
    JsonlSink,
    MetricsSink,
    REGISTRY,
    Registry,
    RingBufferSink,
    disable,
    emit_event,
    enable,
    enabled,
    gauge,
    get_registry,
    inc,
    log_buckets,
    observe,
    recording,
    reset_enabled,
    tap_scope,
)
from .compile_count import compiles, install as _install_compile_count
from .report import summary, validate
from .spans import Span, span
from .taps import count, tap, tap_dict

__all__ = [
    "DEFAULT_BUCKETS",
    "Histogram",
    "JsonlSink",
    "MetricsSink",
    "REGISTRY",
    "Registry",
    "RingBufferSink",
    "Span",
    "compiles",
    "count",
    "disable",
    "emit_event",
    "enable",
    "enabled",
    "gauge",
    "get_registry",
    "inc",
    "log_buckets",
    "observe",
    "recording",
    "reset_enabled",
    "span",
    "summary",
    "tap",
    "tap_dict",
    "tap_scope",
    "validate",
]

_install_compile_count()

"""Nested wall-clock span tracing aligned with JAX profiles (DESIGN.md
§3.10).

    with obs.span("serving.wave") as sp:
        out = step(...)
        sp.block_on(out)          # honest device timing: wait before stop
        sp.note(fill=0.75)        # extra attrs into the span event

Spans are **host-side**: they only make sense *outside* jit-compiled code
— inside a trace, wall time is meaningless and the right tool is a tap
(obs/taps.py).  Two switches govern a span:

  * **always** it enters a ``jax.profiler.TraceAnnotation`` named
    ``repro.<name>`` (a near no-op unless a profiler session is active),
    so every profile of the program names its host regions;
  * **only when observability is enabled** does it also time the region
    with ``time.perf_counter``, block on its ``block=`` target and record
    the duration (the ``span.<name>`` histogram and a ``span`` event).

JAX dispatch is async: without blocking, a span measures enqueue time, not
compute.  ``block=`` / :meth:`Span.block_on` make the span
``jax.block_until_ready`` the given pytree *inside* the timed window —
the explicit opt-in for honest device timing (blocking in the hot path is
a real synchronisation cost, so it is never implicit, and never happens
with observability disabled).

Nesting is tracked with a contextvar stack: each span event records its
``path`` (slash-joined ancestry), ``depth`` and ``start_ns``, its start on
the clock the profiler stamps host events with (``time.time_ns``; an
``.xplane.pb`` stores its times relative to the ``profile_start_time``
stat of its ``Task Environment`` plane), so a flight record and a profile
of one run line up."""
from __future__ import annotations

import contextlib
import time
from contextvars import ContextVar

import jax

from ..runtime import trace_state_clean
from . import registry

_stack: ContextVar[tuple[str, ...]] = ContextVar("repro_obs_spans", default=())


class Span:
    """One live span: attach attrs / a block target while inside it."""

    __slots__ = ("name", "path", "depth", "attrs", "_block")

    def __init__(self, name: str, path: str, depth: int):
        self.name = name
        self.path = path
        self.depth = depth
        self.attrs: dict = {}
        self._block = None

    def note(self, **attrs) -> None:
        """Attach extra key/values to the span event (fill ratios, sizes)."""
        self.attrs.update(attrs)

    def block_on(self, value) -> None:
        """Block on ``value`` (any pytree of arrays) before the span closes,
        so the recorded duration includes device execution, not just
        dispatch."""
        self._block = value


class _NullSpan:
    """Shared no-op stand-in yielded when observability is disabled."""

    __slots__ = ()

    def note(self, **attrs) -> None:
        pass

    def block_on(self, value) -> None:
        pass


_NULL = _NullSpan()


@contextlib.contextmanager
def span(name: str, *, block=None, **attrs):
    """Annotate a host-side region as ``repro.<name>``; when observability
    is enabled, also time it as a nested span named ``name``.

    ``block`` (or :meth:`Span.block_on` inside the region) opts into
    device-honest timing; ``attrs`` seed the span event's attributes.
    A no-op under an active jax trace: span wall-clock is host time, which
    is meaningless while tracing (instrumented eager code called from
    inside someone else's jit must not record trace time as a span)."""
    if not trace_state_clean():
        yield _NULL
        return
    with jax.profiler.TraceAnnotation(f"repro.{name}"):
        if not registry.enabled():
            yield _NULL
            return
        parent = _stack.get()
        path = "/".join((*parent, name))
        token = _stack.set((*parent, name))
        sp = Span(name, path, depth=len(parent))
        if attrs:
            sp.note(**attrs)
        if block is not None:
            sp.block_on(block)
        start_ns = time.time_ns()
        t0 = time.perf_counter()
        try:
            yield sp
            if sp._block is not None:
                jax.block_until_ready(sp._block)
        finally:
            dur = time.perf_counter() - t0
            _stack.reset(token)
            registry.REGISTRY.observe(f"span.{name}", dur)
            event = {
                "type": "span",
                "name": name,
                "path": path,
                "depth": sp.depth,
                "start_ns": start_ns,
                "dur_s": dur,
                "blocked": sp._block is not None,
            }
            if sp.attrs:
                event["attrs"] = sp.attrs
            registry.REGISTRY.emit(event)

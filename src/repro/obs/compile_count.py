"""Every XLA compile of the process, counted whatever the switch says
(DESIGN.md §3.10).

One ``jax.monitoring`` duration listener on
``/jax/core/compile/backend_compile_duration`` — the event jax records
around each backend compile, a persistent-cache hit included — counts
compiles and their seconds by jitted function (the listener's
``fun_name``).  It runs only when something compiles, so a warm hot path
pays nothing.  :func:`compiles` returns a snapshot; each compile's end is
stamped on the profiler's host clock (``time.time_ns``), so a reader can
tell a compile inside a profiled window from one in set-up.  With
observability enabled, each compile is also a ``jit.compile`` event in the
flight record.
"""
from __future__ import annotations

import collections
import threading
import time

from . import registry

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_by_function: dict[str, list] = {}          # fun_name -> [count, seconds]
# (end_ns, seconds, fun_name) of the newest compiles; a long-lived server
# keeps its first compiles in the totals, not here.
_recent: collections.deque = collections.deque(maxlen=4096)
_installed = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    end_ns = time.time_ns()
    fun = str(kwargs.get("fun_name", ""))
    with _lock:
        tally = _by_function.setdefault(fun, [0, 0.0])
        tally[0] += 1
        tally[1] += duration
        _recent.append((end_ns, duration, fun))
    registry.emit_event({"type": "jit.compile", "fun_name": fun,
                         "dur_s": duration, "end_ns": end_ns})


def install() -> None:
    """Register the listener once per process (``repro.obs`` does on
    import)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compiles() -> dict:
    """Snapshot: ``count`` and ``seconds`` in all, ``by_function``
    (``{fun_name: {"count", "seconds"}}``) and ``recent``, the newest
    compiles as ``(end_ns, seconds, fun_name)`` in order."""
    with _lock:
        by_fun = {k: {"count": c, "seconds": s}
                  for k, (c, s) in _by_function.items()}
        recent = list(_recent)
    return {
        "count": sum(v["count"] for v in by_fun.values()),
        "seconds": sum(v["seconds"] for v in by_fun.values()),
        "by_function": by_fun,
        "recent": recent,
    }

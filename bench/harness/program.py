"""What the program names and counts of itself, read for per-layer metrics.

  * Name scopes: the program runs each sparse product under a scope of its
    own (``repro.kernels.dispatch.SCOPES``: ``grf_walks``, ``grf_phi``,
    ``grf_phi_t``, ...), so a device operation's scope path (``tf_op``)
    says which product it belongs to, whatever XLA numbers its fusion.
    :func:`scope_s` sums device time under one scope, optionally only
    where it nests inside another (``grf_phi_t`` inside ``cg_solve``).
  * Compiles: the program counts every compile of its process
    (``repro.obs.compiles``), each stamped with its end on the profiler's
    host clock.  :func:`compiles` splits them into those of set-up (ended
    before the traced window) and those inside the window.

A program older than these names has neither.  There each reader that
reads them gives 0 (see :func:`names_scopes`, :func:`counts_compiles`):
``bench/run.py`` ends a run whose reader finds nothing, and the readers of
a new metric also run over such a program.
"""
from __future__ import annotations

import functools
import os
import re
import sys

from harness import trace

# One component of a scope path, transforms taken off: ``jvp(cg_solve)``
# and ``transpose(jvp(grf_phi_t))`` are ``cg_solve`` and ``grf_phi_t``.
_UNWRAP = re.compile(r"(?:[\w.-]+\()*([^()]*)\)*")


def scope_names(path: str) -> list:
    """The scope names along an op's ``tf_op`` path, outermost first."""
    names = []
    for part in path.split(":")[0].split("/"):
        for piece in part.split(";"):
            m = _UNWRAP.fullmatch(piece)
            names.append(m.group(1) if m else piece)
    return names


def _under(names: list, scopes: tuple, within: str | None) -> bool:
    for i, name in enumerate(names):
        if name in scopes:
            return within is None or within in names[:i]
    return False


def scope_s(tr, scopes, within: str | None = None, program: str = "",
            chip: int = 0) -> float:
    """Device seconds (union, inside the window) of the operations under
    any of the name scopes ``scopes`` (one name or several) — nested inside
    ``within`` where given — in programs whose name contains ``program``."""
    scopes = (scopes,) if isinstance(scopes, str) else tuple(scopes)
    return tr.union_s(ev for ev in tr.ops.get(chip, [])
                      if program in ev.program
                      and _under(scope_names(ev.scope), scopes, within))


def names_scopes() -> bool:
    """Whether the program under test names its products' scopes."""
    from repro.kernels import dispatch

    return hasattr(dispatch, "SCOPES")


def counts_compiles() -> bool:
    """Whether the program under test counts its compiles."""
    from repro import obs

    return hasattr(obs, "compiles")


def _trace_dir(run) -> str:
    """The directory the run profiled its window into (``TRACE_DIR`` of the
    module that made ``run``: ``bench/run.py``)."""
    return sys.modules[type(run).__module__].TRACE_DIR


@functools.lru_cache(maxsize=4)
def _profile_start_ns(path: str, mtime: float) -> int | None:
    import jax

    plane = jax.profiler.ProfileData.from_file(path).find_plane_with_name(
        "Task Environment")
    start = dict(plane.stats).get("profile_start_time") if plane else None
    return None if start is None else int(start)


def profile_start_ns(run) -> int | None:
    """The host-clock time (``time.time_ns``) that the trace's times count
    from: the ``profile_start_time`` stat of its ``Task Environment``
    plane."""
    path = trace.newest_xplane(_trace_dir(run))
    return _profile_start_ns(path, os.path.getmtime(path))


def compiles(run):
    """``(setup, window)``: the compiles the program counted that ended
    before the traced window and inside it, each ``(end_ns, seconds,
    fun_name)``; None when the trace does not say when it started."""
    from repro import obs

    start = profile_start_ns(run)
    if start is None:
        return None
    lo, hi = (start + round(t * 1e9) for t in run.trace.window)
    recent = obs.compiles()["recent"]
    return ([c for c in recent if c[0] < lo],
            [c for c in recent if lo <= c[0] <= hi])

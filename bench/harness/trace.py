"""Profiler capture and the reduction from a device trace to numbers.

A ``--trace 1`` run wraps its window in :func:`capture`; the profiler writes
an ``.xplane.pb`` that :func:`load` reduces to a :class:`Trace`: every
device operation as (start, end, name, program, scope path) per chip, and
the benchmark's own host annotations (``bench.*``) from the Python thread.
Per-layer readers (``bench/metrics/*.py``) ask the :class:`Trace` for
device time per program, per name scope, the busy union and the idle gaps.

Clock: the profiler puts host and device events on one time base, so a
host annotation and the device operations inside it can be compared.
"""
from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
import shutil

WINDOW = "bench.window"          # the host annotation that spans the window
HOST_PREFIX = "bench."           # annotations the benchmark puts around calls


@dataclasses.dataclass
class Op:
    start: float                 # seconds, profiler time base
    end: float
    name: str                    # HLO op name
    program: str                 # HLO module (jitted program) name
    scope: str                   # name-scope path (tf_op) where the op
                                 # has one, else its string stats joined
    run: str = ""                # the program run it belongs to (run_id)
    category: str = ""           # hlo_category: "while" holds its body's ops


@dataclasses.dataclass
class Trace:
    ops: dict                    # chip index -> list[Op] sorted by start
    modules: dict                # chip index -> list[Op] (one per program run)
    host: list                   # [(start, end, name)] bench.* annotations
    window: tuple                # (start, end) of the bench.window span

    # -- the window --------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _in_window(self, evs):
        lo, hi = self.window
        for ev in evs:
            s, e = max(ev.start, lo), min(ev.end, hi)
            if e > s:
                yield s, e, ev

    # -- busy / idle -------------------------------------------------------
    def busy_intervals(self, chip: int) -> list:
        """Union of device-operation intervals on ``chip`` inside the window."""
        spans = sorted((s, e) for s, e, _ in self._in_window(
            self.ops.get(chip, [])))
        out = []
        for s, e in spans:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips in the trace."""
        chips = sorted(self.ops) or [0]
        total = sum(sum(e - s for s, e in self.busy_intervals(c))
                    for c in chips)
        return total / len(chips)

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self.ops:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def idle_gaps(self, chip: int = 0, top: int = 10) -> list:
        """Idle seconds inside the window, by the innermost host annotation
        that covers each gap's midpoint (``host:unannotated`` when none
        does).  Annotations of one name never overlap each other: each
        marks one call at a time on the benchmark's thread."""
        lo, hi = self.window
        gaps, t = [], lo
        for s, e in self.busy_intervals(chip):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        by_name_spans = collections.defaultdict(list)
        for hs, he, name in self.host:
            if name != WINDOW:
                by_name_spans[name].append((hs, he))
        index = {name: ([s for s, _ in v], v)
                 for name, v in by_name_spans.items()}
        acc = collections.Counter()
        for gs, ge in gaps:
            mid, best, best_len = 0.5 * (gs + ge), "host:unannotated", None
            for name, (starts, spans) in index.items():
                j = bisect.bisect_right(starts, mid) - 1
                if j >= 0 and spans[j][1] >= mid:
                    length = spans[j][1] - spans[j][0]
                    if best_len is None or length < best_len:
                        best, best_len = name, length
            acc[best] += ge - gs
        return [[k, v] for k, v in acc.most_common(top)]

    # -- device time -------------------------------------------------------
    def program_s(self, program: str, chip: int = 0) -> float:
        """Device seconds of runs of jitted programs whose module name
        contains ``program`` (whole-program spans), inside the window."""
        mods = self.modules.get(chip, [])
        if mods:
            return sum(e - s for s, e, ev in self._in_window(mods)
                       if program in ev.name)
        return self.union_s(ev for ev in self.ops.get(chip, [])
                            if program in ev.program)

    def program_runs(self, program: str, chip: int = 0) -> int:
        """Runs of programs whose module name contains ``program``."""
        mods = self.modules.get(chip, [])
        if mods:
            return sum(1 for _, _, ev in self._in_window(mods)
                       if program in ev.name)
        return len({ev.run for _, _, ev in self._in_window(
            self.ops.get(chip, [])) if program in ev.program})

    def scope_s(self, scope: str, chip: int = 0) -> float:
        """Device seconds of operations under name scope ``scope``: a
        whole path component, also where a transform wraps it
        (``jvp(cg_solve)``, ``transpose(jvp(cg_solve))``)."""
        pat = re.compile(rf"(^|[/\s])(?:[\w.-]+\()*{re.escape(scope)}\)*"
                         r"(?=[/:\s]|$)")
        return self.union_s(ev for ev in self.ops.get(chip, [])
                            if pat.search(ev.scope))

    def kernel_s(self, name_part: str, program: str = "",
                 chip: int = 0) -> float:
        """Device seconds of operations whose name or scope contains
        ``name_part``, inside programs whose name contains ``program``."""
        return self.union_s(ev for ev in self.ops.get(chip, [])
                            if program in ev.program
                            and name_part in ev.name + " " + ev.scope)

    def union_s(self, evs) -> float:
        """Seconds covered by the union of ``evs`` inside the window."""
        spans = sorted((s, e) for s, e, _ in self._in_window(evs))
        total, cur_s, cur_e = 0.0, None, None
        for s, e in spans:
            if cur_e is not None and s <= cur_e:
                cur_e = max(cur_e, e)
                continue
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        if cur_e is not None:
            total += cur_e - cur_s
        return total

    def summary(self) -> str:
        """What the trace holds, in one line: for a run whose reader found
        nothing, to show which name is missing."""
        ops = [o for evs in self.ops.values() for o in evs]
        progs = collections.Counter(o.program for o in ops)
        mods = collections.Counter(
            o.name for evs in self.modules.values() for o in evs)
        scopes = collections.Counter(o.scope.split(":")[0][:80] for o in ops)
        return (f"{len(ops)} device ops; programs {progs.most_common(4)}; "
                f"program runs {mods.most_common(4)}; "
                f"scopes {scopes.most_common(4)}; "
                f"window {self.window_s:.3f} s")

    def top_ops(self, chip: int = 0, top: int = 10) -> list:
        """Device operations that took most time, by program, op name and
        the tail of its name scope.  A loop's own span, which holds its
        body's operations, is left out."""
        acc = collections.Counter()
        for s, e, ev in self._in_window(self.ops.get(chip, [])):
            if ev.category in CONTROL_FLOW:
                continue
            path = ev.scope.split(":")[0]
            tail = "/".join(path.split("/")[-4:]) if "/" in path else ""
            acc[f"{ev.program}/{ev.name} {tail}".strip()] += e - s
        return [[k, v] for k, v in acc.most_common(top)]


@contextlib.contextmanager
def capture(directory: str):
    """Profile the enclosed block into ``directory`` (emptied first).  The
    Python tracer stays off: it would record every Python call of the
    host's loop, slow it and widen the idle gaps it is meant to explain;
    the benchmark's own annotations name those gaps."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """A host span in the profiler's trace (costs ~µs when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def newest_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
CONTROL_FLOW = ("while", "conditional", "call")
_DEVICE_LINES = ("XLA Ops", "XLA Modules")

# tsl/profiler/protobuf/xplane.proto, field by field: (name, number, type,
# repeated).  A type that is a str names a message of this table.  Maps are
# read as the repeated key/value entries they are on the wire.
_I64, _U64, _F64, _STR, _BYTES = 3, 4, 1, 9, 12
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, _I64, False), ("name", 2, _STR, False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True),
               ("stats", 6, "XStat", True)],
    "EventMetadataEntry": [("key", 1, _I64, False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, _I64, False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("id", 1, _I64, False), ("display_id", 10, _I64, False),
              ("name", 2, _STR, False), ("display_name", 11, _STR, False),
              ("timestamp_ns", 3, _I64, False),
              ("duration_ps", 9, _I64, False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, _I64, False), ("offset_ps", 2, _I64, False),
               ("num_occurrences", 5, _I64, False),
               ("duration_ps", 3, _I64, False), ("stats", 4, "XStat", True)],
    "XStat": [("metadata_id", 1, _I64, False), ("double_value", 2, _F64, False),
              ("uint64_value", 3, _U64, False), ("int64_value", 4, _I64, False),
              ("str_value", 5, _STR, False), ("bytes_value", 6, _BYTES, False),
              ("ref_value", 7, _U64, False)],
    "XEventMetadata": [("id", 1, _I64, False), ("name", 2, _STR, False),
                       ("display_name", 4, _STR, False),
                       ("metadata", 3, _BYTES, False),
                       ("stats", 5, "XStat", True),
                       ("child_id", 6, _I64, True)],
    "XStatMetadata": [("id", 1, _I64, False), ("name", 2, _STR, False),
                      ("description", 3, _STR, False)],
}
_CLASSES: dict = {}


def xspace_class(name: str = "XSpace"):
    """The protobuf message class of ``name`` in :data:`_SCHEMA`."""
    if not _CLASSES:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory)

        fdp = descriptor_pb2.FileDescriptorProto(
            name="bench_xplane.proto", package="bench_xplane", syntax="proto2")
        fd = descriptor_pb2.FieldDescriptorProto
        for msg, fields in _SCHEMA.items():
            m = fdp.message_type.add(name=msg)
            for fname, number, ftype, repeated in fields:
                f = m.field.add(name=fname, number=number,
                                label=(fd.LABEL_REPEATED if repeated
                                       else fd.LABEL_OPTIONAL))
                if isinstance(ftype, str):
                    f.type = fd.TYPE_MESSAGE
                    f.type_name = f".bench_xplane.{ftype}"
                else:
                    f.type = ftype
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        for msg in _SCHEMA:
            _CLASSES[msg] = message_factory.GetMessageClass(
                pool.FindMessageTypeByName(f"bench_xplane.{msg}"))
    return _CLASSES[name]


@dataclasses.dataclass
class _Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: list                  # [(stat name, value)]: metadata's, then own


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


def _stat_list(stats, stat_names: dict) -> list:
    out = []
    for st in stats:
        if st.HasField("str_value"):
            value = st.str_value
        elif st.HasField("ref_value"):
            value = stat_names.get(st.ref_value, "")
        elif st.HasField("int64_value"):
            value = st.int64_value
        elif st.HasField("uint64_value"):
            value = st.uint64_value
        elif st.HasField("double_value"):
            value = st.double_value
        else:
            continue
        out.append((stat_names.get(st.metadata_id, ""), value))
    return out


def planes_from_xspace(data: bytes) -> list:
    """The planes a :class:`Trace` is built from, read from a serialized
    XSpace: each event carries its metadata's stats (on the TPU the op's
    name scopes live there) ahead of its own.  Only the device lines and
    the benchmark's own host annotations are kept."""
    xs = xspace_class()()
    xs.ParseFromString(data)
    planes = []
    for p in xs.planes:
        device = bool(_DEVICE_PLANE.match(p.name))
        if not device and not p.name.startswith("/host:"):
            continue
        stat_names = {e.key: e.value.name for e in p.stat_metadata}
        meta = {e.key: e.value for e in p.event_metadata}
        meta_stats: dict = {}
        lines = []
        for ln in p.lines:
            if device and ln.name not in _DEVICE_LINES:
                continue
            events = []
            for ev in ln.events:
                md = meta.get(ev.metadata_id)
                # On the TPU an op's name is its whole HLO instruction and
                # its display name the instruction's own name.
                name = (md.display_name or md.name) if md is not None else ""
                if not device and not name.startswith(HOST_PREFIX):
                    continue
                if ev.metadata_id not in meta_stats:
                    meta_stats[ev.metadata_id] = (
                        _stat_list(md.stats, stat_names) if md is not None
                        else [])
                events.append(_Event(
                    name=name,
                    start_ns=ln.timestamp_ns + ev.offset_ps * 1e-3,
                    duration_ns=ev.duration_ps * 1e-3,
                    stats=meta_stats[ev.metadata_id]
                    + _stat_list(ev.stats, stat_names)))
            lines.append(_Line(name=ln.name, events=events))
        planes.append(_Plane(name=p.name, lines=lines))
    return planes


def load(path: str) -> Trace:
    """Reduce an ``.xplane.pb`` to a :class:`Trace`."""
    with open(path, "rb") as fh:
        return from_planes(planes_from_xspace(fh.read()))


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def from_planes(planes) -> Trace:
    """Build a :class:`Trace` from profiler planes (:func:`planes_from_xspace`
    or any objects with the same ``name``/``lines``/``events``/``stats``
    shape)."""
    ops, modules, host = {}, {}, []
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dest = ops.setdefault(chip, [])
                elif line.name == "XLA Modules":
                    dest = modules.setdefault(chip, [])
                else:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    strings = [str(v) for v in st.values() if isinstance(v, str)]
                    dest.append(Op(
                        start=ev.start_ns * 1e-9,
                        end=(ev.start_ns + ev.duration_ns) * 1e-9,
                        name=ev.name,
                        program=str(st.get("hlo_module", "")),
                        scope=str(st["tf_op"] if "tf_op" in st
                                  else " ".join(strings)),
                        run=str(st.get("run_id", "")),
                        category=str(st.get("hlo_category", "")),
                    ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append((ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9,
                                     ev.name))
    for d in (ops, modules):
        for evs in d.values():
            evs.sort(key=lambda o: o.start)
    for chip, evs in ops.items():
        _name_programs(evs, modules.get(chip, []))
    windows = [(s, e) for s, e, n in host if n == WINDOW]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        everything = [o for evs in ops.values() for o in evs]
        window = ((min(o.start for o in everything),
                   max(o.end for o in everything)) if everything else (0., 0.))
    return Trace(ops=ops, modules=modules, host=sorted(host), window=window)


def _name_programs(ops: list, modules: list) -> None:
    """Give an operation that carries no module name the name of the
    program run whose span holds it."""
    starts = [m.start for m in modules]
    for op in ops:
        if op.program or not modules:
            continue
        j = bisect.bisect_right(starts, op.start) - 1
        if j >= 0 and modules[j].end >= op.end:
            op.program = modules[j].name

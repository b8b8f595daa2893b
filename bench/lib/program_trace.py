"""The program's own spans and name scopes in a traced run.

The program marks its host work with ``repro.<name>`` annotations
(``repro.obs.span``; attributes such as ``what`` or ``tick`` ride as the
event's stats) and its device work with ``jax.named_scope``
(``protocol.aggregate``, ``ocs.sense``).  On a TPU trace a device
operation's scope path is the ``tf_op`` stat of its event metadata, for
example ``jit(_tick)/while/body/closed_call/protocol.aggregate/ocs.sense/
shift_left:`` (``jvp(protocol.aggregate)`` under differentiation).
``jax.profiler.ProfileData`` does not expose event metadata, so
:func:`read_op_stats` decodes those few fields of the ``.xplane.pb`` with
a schema of its own.

:func:`load` reads the traced run's ``.xplane.pb`` once per process
(``bench/lib/trace.py`` finds it and splits its planes) and yields a
:class:`ProgramTrace`: the ``repro.*`` host spans that overlap the
``bench.window`` span, and every device operation in the window (loops and
calls left out, as in ``trace.reduce_trace``) with its scope path.  The
arithmetic after that is tested on a hand-built trace.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Tuple

from bench.lib import trace as T

SPAN_PREFIX = "repro."
Span = Tuple[str, int, int, dict]     # (name without prefix, start, end, attrs)
Op = Tuple[str, int, int, str]        # (event text, start, end, scope path)


@dataclasses.dataclass
class ProgramTrace:
    window: T.Interval
    spans: List[Span]                 # overlapping the window, not clipped
    ops: Dict[str, List[Op]]          # device plane -> ops clipped to it

    def starting(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [sp for sp in self.spans if sp[0] == name and lo <= sp[1] < hi]

    def ending(self, name: str) -> List[Span]:
        lo, hi = self.window
        return [sp for sp in self.spans if sp[0] == name and lo < sp[2] <= hi]

    def covered(self, name: str) -> List[T.Interval]:
        """The union of the spans ``name``, clipped to the window."""
        return T.merge((s, e) for _, s, e in T.clip(
            ((n, s, e) for n, s, e, _ in self.spans if n == name),
            self.window))


# ---------------------------------------------------------------------------
# from planes
# ---------------------------------------------------------------------------

def program_spans(planes: Iterable) -> List[Span]:
    """The ``repro.*`` events of the host planes, with their stats."""
    out = []
    for plane in planes:
        if not str(plane.name).startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(SPAN_PREFIX):
                    s = int(ev.start_ns)
                    out.append((name[len(SPAN_PREFIX):], s,
                                s + int(ev.duration_ns), dict(ev.stats)))
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether one component of ``path`` is ``scope``, bare or wrapped by
    a transformation (``jvp(scope)``, ``transpose(jvp(scope))``)."""
    return re.search(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/):]|$)",
                     path) is not None


def build(planes: List, op_stats: Dict[str, dict]) -> ProgramTrace:
    """A :class:`ProgramTrace` from objects shaped like
    ``ProfileData.planes`` (host events carry ``.stats``) and each device
    event text's metadata stats."""
    trace = T.from_planes(planes)
    window = T.window_of(trace)
    lo, hi = window
    spans = [sp for sp in program_spans(planes) if sp[1] < hi and sp[2] > lo]
    ops = {dev: [(text, s, e, op_stats.get(text, {}).get("tf_op", ""))
                 for text, s, e in T.clip(events, window)
                 if not T.is_container(text)]
           for dev, events in trace.ops.items()}
    return ProgramTrace(window=window, spans=spans, ops=ops)


# ---------------------------------------------------------------------------
# the .xplane.pb's event metadata
# ---------------------------------------------------------------------------

_XSPACE = None


def _xspace_class():
    """A message class for the few XSpace fields read here: per plane its
    name and the id, name and stats of its event and stat metadata (field
    numbers of ``tsl/profiler/protobuf/xplane.proto``; a map is a repeated
    key-value message on the wire)."""
    global _XSPACE
    if _XSPACE is not None:
        return _XSPACE
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
              "bytes": F.TYPE_BYTES}
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto2")

    def message(name, *fields):
        """``fields``: (name, number, kind); a kind is a scalar type, a
        message name, or ``*`` and a message name for a repeated one."""
        m = f.message_type.add(name=name)
        for fname, number, kind in fields:
            fd = m.field.add(name=fname, number=number,
                             label=F.LABEL_OPTIONAL)
            if kind in scalar:
                fd.type = scalar[kind]
                continue
            fd.type = F.TYPE_MESSAGE
            if kind.startswith("*"):
                fd.label, kind = F.LABEL_REPEATED, kind[1:]
            fd.type_name = f".bench_xplane.{kind}"

    message("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "bytes"),
            ("ref_value", 7, "uint64"))
    message("XEventMetadata", ("name", 2, "bytes"), ("stats", 5, "*XStat"))
    message("XStatMetadata", ("name", 2, "bytes"))
    message("EventEntry", ("key", 1, "int64"),
            ("value", 2, "XEventMetadata"))
    message("StatEntry", ("key", 1, "int64"), ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "bytes"),
            ("event_metadata", 4, "*EventEntry"),
            ("stat_metadata", 5, "*StatEntry"))
    message("XSpace", ("planes", 1, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    _XSPACE = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _XSPACE


def _text(raw: bytes) -> str:
    return raw.decode("utf-8", "replace")


def read_op_stats(path: pathlib.Path) -> Dict[str, dict]:
    """Per device event text (the name ``ProfileData`` gives the event),
    the string stats of its metadata (``tf_op`` among them)."""
    space = _xspace_class()()
    space.ParseFromString(pathlib.Path(path).read_bytes())
    out: Dict[str, dict] = {}
    for plane in space.planes:
        if not _text(plane.name).startswith("/device:"):
            continue
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            md = entry.value
            stats = {}
            for st in md.stats:
                value = (_text(st.str_value) if st.HasField("str_value")
                         else stat_names.get(st.ref_value)
                         if st.HasField("ref_value") else None)
                if value is not None:
                    stats[stat_names.get(st.metadata_id, "")] = value
            out.setdefault(_text(md.name), stats)
    return out


_LOADED: Dict[tuple, ProgramTrace] = {}


def load(run) -> Optional[ProgramTrace]:
    """The traced run's :class:`ProgramTrace`, read once per trace file;
    ``None`` for a run that was not traced."""
    if run.summary is None:
        return None
    import jax

    from bench.lib import harness as H

    path = T.find_xplane(H.TRACE_DIR)
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _LOADED:
        planes = list(jax.profiler.ProfileData.from_file(str(path)).planes)
        _LOADED.clear()
        _LOADED[key] = build(planes, read_op_stats(path))
    return _LOADED[key]


# ---------------------------------------------------------------------------
# the metrics' arithmetic
# ---------------------------------------------------------------------------

def per_tick(pt: Optional[ProgramTrace], name: str) -> Optional[float]:
    """Spans ``name`` starting in the window, per ``serve.tick`` span
    starting in it."""
    ticks = len(pt.starting("serve.tick")) if pt else 0
    return len(pt.starting(name)) / ticks if ticks else None


def idle_inside_ns(pt: ProgramTrace, name: str) -> float:
    """Device idle time (no operation running) inside the union of the
    spans ``name``, in the window, averaged over the devices."""
    inside = pt.covered(name)
    per_dev = []
    for ops in pt.ops.values():
        idle = T.subtract([pt.window], T.merge((s, e) for _, s, e, _ in ops))
        per_dev.append(T.union_ns(idle)
                       - T.union_ns(T.subtract(idle, inside)))
    return sum(per_dev) / len(per_dev)


def idle_inside_ms_per_tick(pt: Optional[ProgramTrace], name: str
                            ) -> Optional[float]:
    """:func:`idle_inside_ns` per ``serve.tick`` span starting in the
    window, in ms."""
    ticks = len(pt.starting("serve.tick")) if pt else 0
    if not ticks or not pt.ops:
        return None
    return idle_inside_ns(pt, name) / ticks / 1e6


def mean_ms(pt: Optional[ProgramTrace], name: str) -> Optional[float]:
    """Mean duration of the spans ``name`` that end in the window."""
    spans = pt.ending(name) if pt else []
    if not spans:
        return None
    return sum(e - s for _, s, e, _ in spans) / len(spans) / 1e6


def scope_share(pt: Optional[ProgramTrace], scope: str) -> Optional[float]:
    """Device time of the operations under ``scope``, in percent of the
    device time of every operation in the window; ``None`` where no
    operation is under it (a program without the scope)."""
    if pt is None:
        return None
    total = part = 0
    for ops in pt.ops.values():
        for _, s, e, path in ops:
            total += e - s
            if in_scope(path, scope):
                part += e - s
    return 100.0 * part / total if part else None

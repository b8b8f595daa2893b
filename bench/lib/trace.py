"""Profiler capture and the reduction from a device trace to metrics.

A traced run wraps its measured window in :func:`capture`, which records a
JAX profiler trace (an ``.xplane.pb``) with the Python tracer off.  The
harness marks the window and the host work inside it with
``jax.profiler.TraceAnnotation`` spans named ``bench.<what>``, so they land
on the same clock as the device's operations.

:func:`load_xplane` turns the trace into plain lists: per device, its
operation events (the ``XLA Ops`` line) and its program events (the
``XLA Modules`` line), and the harness's host spans.  Everything after that
is arithmetic on ``(name, start_ns, end_ns)`` tuples, tested on a small
hand-built trace:

* :func:`union_ns` - the time covered by a set of intervals;
* :func:`reduce_trace` - per device, the busy union of operations inside
  the window (loops and calls left out: their bodies' operations are
  events of their own), device time by operation and by program, the time
  a collective ran with no compute beside it, and the idle gaps, each
  attributed to the host span that covered most of it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE_MARKERS = ("all-gather", "all-reduce", "reduce-scatter",
                      "collective-permute", "all-to-all", "allgather",
                      "allreduce", "reducescatter")


CONTAINERS = ("while", "conditional", "call")


def is_collective(name: str) -> bool:
    low = name.lower()
    return any(m in low for m in COLLECTIVE_MARKERS)


def short_name(text: str) -> str:
    """An operation event's instruction name (TPU traces name each event
    by the instruction's whole HLO text: ``%fusion.12 = ...``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_container(text: str) -> bool:
    """A loop or call whose body's operations are events of their own."""
    return short_name(text).split(".", 1)[0] in CONTAINERS


@contextlib.contextmanager
def capture(out_dir: pathlib.Path):
    """Record a JAX profiler trace of the enclosed block into ``out_dir``."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir: pathlib.Path) -> pathlib.Path:
    paths = sorted(pathlib.Path(out_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return paths[-1]


@dataclasses.dataclass
class Trace:
    """A trace as plain data: device op/program events and host spans."""

    ops: Dict[str, List[Event]]        # device plane -> operation events
    modules: Dict[str, List[Event]]    # device plane -> program events
    spans: List[Event]                 # harness host spans (bench.*)


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append((str(ev.name), start, start + int(ev.duration_ns)))
    return out


def from_planes(planes: Iterable) -> Trace:
    """Build a :class:`Trace` from objects shaped like
    ``jax.profiler.ProfileData.planes`` (``.name``, ``.lines`` with
    ``.name`` and ``.events`` carrying ``.name``, ``.start_ns``,
    ``.duration_ns``).  Device planes are those named ``/device:<X>:<n>``
    (one per chip; auxiliary planes such as SparseCores carry a further
    word and are left out)."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in planes:
        name = str(plane.name)
        if name.startswith("/device:") and " " not in name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(name, []).extend(_events(line))
                elif line.name == "XLA Modules":
                    modules.setdefault(name, []).extend(_events(line))
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[0].startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def load_xplane(path: pathlib.Path) -> Trace:
    import jax

    return from_planes(jax.profiler.ProfileData.from_file(str(path)).planes)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those wholly outside are dropped."""
    lo, hi = window
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of the intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap_ns(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Summary:
    """What a traced window says, averaged over the devices where noted."""

    window_ns: int
    n_devices: int
    busy_ns: float                        # mean over devices
    collective_exposed_ns: float          # mean over devices
    op_ns: Dict[str, int]                 # by short name, summed over devices
    op_text: Dict[str, str]               # short name -> the event's text
    module_ns: Dict[str, int]             # summed over devices
    module_count: Dict[str, int]          # summed over devices
    gaps: List[Tuple[str, int]]           # (host span, ns), longest first

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def ops_matching(self, marker: str) -> int:
        """Summed device time of the operations whose text holds
        ``marker``."""
        return sum(ns for name, ns in self.op_ns.items()
                   if marker in self.op_text[name])

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[n, ns / 1e9] for n, ns in self.gaps[:top]]}


def window_of(trace: Trace) -> Interval:
    wins = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    return wins[0]


def attribute(gap: Interval, spans: Sequence[Event]) -> str:
    """The harness span that covers most of ``gap`` (the shorter one on a
    tie): the host work the device waited on.  ``none`` where no span
    other than the window's was open."""
    cands = [(overlap_ns(gap, (s, e)), s - e, name)
             for name, s, e in spans if name != WINDOW_SPAN]
    cands = [c for c in cands if c[0] > 0]
    if not cands:
        return "none"
    return max(cands)[2][len(SPAN_PREFIX):]


def reduce_trace(trace: Trace, window: Optional[Interval] = None
                 ) -> Summary:
    """Reduce a trace to a :class:`Summary` over ``window`` (default: the
    ``bench.window`` span)."""
    if window is None:
        window = window_of(trace)
    lo, hi = window
    devices = sorted(trace.ops)
    spans = clip(trace.spans, window)
    busy, exposed = [], []
    op_ns: Dict[str, int] = {}
    op_text: Dict[str, str] = {}
    module_ns: Dict[str, int] = {}
    module_count: Dict[str, int] = {}
    gaps: List[Tuple[str, int]] = []
    for dev in devices:
        ops = [e for e in clip(trace.ops[dev], window)
               if not is_container(e[0])]
        busy_iv = merge((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in busy_iv))
        coll = merge((s, e) for n, s, e in ops if is_collective(n))
        comp = merge((s, e) for n, s, e in ops if not is_collective(n))
        exposed.append(sum(e - s for s, e in subtract(coll, comp)))
        for text, s, e in ops:
            name = short_name(text)
            op_text.setdefault(name, text)
            op_ns[name] = op_ns.get(name, 0) + (e - s)
        for name, s, e in clip(trace.modules.get(dev, []), window):
            module_ns[name] = module_ns.get(name, 0) + (e - s)
            module_count[name] = module_count.get(name, 0) + 1
        for g in subtract([(lo, hi)], busy_iv):
            gaps.append((attribute(g, spans), g[1] - g[0]))
    n = max(len(devices), 1)
    gaps.sort(key=lambda kv: -kv[1])
    return Summary(window_ns=hi - lo, n_devices=len(devices),
                   busy_ns=sum(busy) / n, collective_exposed_ns=sum(exposed) / n,
                   op_ns=op_ns, op_text=op_text, module_ns=module_ns,
                   module_count=module_count, gaps=gaps)

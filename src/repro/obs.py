"""Observability: named host spans and integer counters.

One registry of counters for the whole package, and one kind of span:

* :func:`count` / :func:`counts` / :func:`reset` - integer counters by
  name.  The engines' ``trace_counts()`` / ``dispatch_counts()`` are views
  of it under their own prefixes (``serve.trace.``, ``curves.dispatch.``,
  ...).
* :func:`span` - a context manager around host work.  It counts its name,
  opens a ``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (so a
  profiler trace holds it on the same clock as the device's operations,
  its attributes as the event's stats), and, inside :func:`recording`,
  keeps ``(name, start, end, attrs)`` on the ``time.perf_counter`` clock
  in memory.
* :func:`recording` - the context that keeps spans in memory.

Without a recording and without a profiler, a span costs a counter
increment and an inactive annotation.  The clock is read here only: what a
span or counter measures never feeds a decision of the engines that open
them.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, Optional, Tuple

import jax

Span = Tuple[str, float, float, dict]

_COUNTS: Dict[str, int] = {}
_RECORD: Optional[List[Span]] = None


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _COUNTS[name] = _COUNTS.get(name, 0) + n


def counts(prefix: str = "") -> Dict[str, int]:
    """The counters whose names start with ``prefix``."""
    return {k: v for k, v in _COUNTS.items() if k.startswith(prefix)}


def reset(prefix: str = "") -> None:
    """Zero the counters whose names start with ``prefix``."""
    for k in [k for k in _COUNTS if k.startswith(prefix)]:
        del _COUNTS[k]


def view(prefix: str, keys) -> Dict[str, int]:
    """The counters ``prefix + key`` for every key, by key (0 where
    nothing was counted yet)."""
    return {k: _COUNTS.get(prefix + k, 0) for k in keys}


def span(name: str, **attrs):
    """``with span(name, **attrs):`` - a named, counted host span; see the
    module docstring."""
    _COUNTS[name] = _COUNTS.get(name, 0) + 1
    if _RECORD is None:
        return jax.profiler.TraceAnnotation("repro." + name, **attrs)
    return _Recorded(name, attrs, _RECORD)


class _Recorded:
    """A span inside :func:`recording`: annotated and kept in memory."""

    def __init__(self, name: str, attrs: dict, into: List[Span]):
        self.name, self.attrs, self.into = name, attrs, into
        self.ann = jax.profiler.TraceAnnotation("repro." + name, **attrs)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.into.append((self.name, self.t0, time.perf_counter(),
                          self.attrs))
        self.ann.__exit__(*exc)
        return False


@contextlib.contextmanager
def recording() -> Iterator[List[Span]]:
    """Keep every span closed inside the block; yields the list they are
    appended to, in the order they close."""
    global _RECORD
    outer, _RECORD = _RECORD, []
    try:
        yield _RECORD
    finally:
        _RECORD = outer

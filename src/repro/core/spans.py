"""Program spans and counters: the one timing mechanism of the program.

``span(name, **attrs)`` times a block twice over:

- as a ``jax.profiler.TraceAnnotation``, so that inside a profiler
  session the span lands in the trace on the profiler's own clock, beside
  the device's operations, with its attrs (and counters) as event args;
- as a record in a bounded in-process buffer (``recorded()``), timed with
  ``time.perf_counter_ns``, so that a reader can sum spans over every job
  of a run, traced or not.

Spans nest per thread: a span opened with none open is a root, and every
span under it carries its ``root_id``, so the spans of one job share an
identifier. ``count(name, n)`` adds to the counters of the innermost open
span and to the process totals (``totals()``). Recording is always on; a
span costs about a microsecond of host time.

JAX runs a jitted function's Python body once, while tracing, so a span
there would time the tracing and not the work: ``span(..., over=arrays)``
does nothing when one of ``arrays`` is a ``jax.core.Tracer``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

# Records kept: a build job opens about 20 spans, a traversal level a few.
CAPACITY = 1 << 16

# The profiler encodes event args as ``name#k=v,k=v#``; these would split
# a string value.
_TRACE_UNSAFE = str.maketrans({",": ";", "=": ":", "#": "_"})


class Span(NamedTuple):
    """One closed span. ``parent_id`` is None for a root."""

    name: str
    span_id: int
    parent_id: Optional[int]
    root_id: int
    start_ns: int
    end_ns: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_buffer: collections.deque = collections.deque(maxlen=CAPACITY)
_totals: collections.Counter = collections.Counter()
_totals_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_Tracer = jax.core.Tracer
_TraceAnnotation = jax.profiler.TraceAnnotation


def _open_spans() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _trace_args(attrs: dict) -> dict:
    return {k: v.translate(_TRACE_UNSAFE) if isinstance(v, str) else v for k, v in attrs.items()}


class span:
    """Context manager for one span; ``with span(...) as sp`` gives the
    open span: ``sp.set(**attrs)`` adds attrs known only after the work,
    ``sp.counters`` holds what ``count`` added, and ``sp.seconds`` is its
    duration once closed."""

    __slots__ = ("name", "attrs", "counters", "skip", "span_id", "parent_id",
                 "root_id", "start_ns", "end_ns", "_annotation")

    def __init__(self, name: str, *, over=(), **attrs):
        self.name = name
        self.attrs = attrs
        self.counters: dict = {}
        self.skip = bool(over) and any(isinstance(a, _Tracer) for a in over)
        self.start_ns = self.end_ns = 0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "span":
        # a skipped span still goes on the stack, so that what a traced
        # body counts is not charged to the span around the trace
        stack = _open_spans()
        parent = stack[-1] if stack else None
        stack.append(self)
        if self.skip:
            return self
        self.span_id = next(_ids)
        self.parent_id = parent.span_id if parent else None
        self.root_id = parent.root_id if parent else self.span_id
        # outside a profiler session the annotation would be a no-op
        self._annotation = None
        if _TraceAnnotation.is_enabled():
            self._annotation = _TraceAnnotation(self.name, **_trace_args(self.attrs))
            self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        _open_spans().pop()
        if self.skip:
            return
        attrs = {**self.attrs, **self.counters} if self.counters else self.attrs
        if self._annotation is not None:
            self._annotation.set_metadata(**_trace_args(attrs))
            self._annotation.__exit__(*exc)
        # a plain tuple: ``recorded()`` makes the ``Span``, off the hot path
        _buffer.append((self.name, self.span_id, self.parent_id, self.root_id,
                        self.start_ns, self.end_ns, attrs))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span of this
    thread (if any) and to the process totals."""
    stack = _open_spans()
    if stack:
        counters = stack[-1].counters
        counters[name] = counters.get(name, 0) + n
    with _totals_lock:
        _totals[name] += n


def recorded() -> list:
    """The closed spans kept, oldest first (each span closes after the
    spans nested in it)."""
    return [Span._make(t) for t in _buffer]


def totals() -> dict:
    """Every counter summed over the process since the last ``reset``."""
    with _totals_lock:
        return dict(_totals)


def reset() -> None:
    """Forget the recorded spans and the process totals."""
    _buffer.clear()
    with _totals_lock:
        _totals.clear()

"""Spans and a compile counter at the program's layer boundaries.

* :func:`span` times a block on one thread.  It enters
  ``jax.profiler.TraceAnnotation``, so under the profiler the span lands
  on the trace's host plane on the device ops' clock, and it appends a
  :class:`Span` record (name, start, end, id, parent, thread, attrs) to a
  process-wide buffer.  The parent is the innermost span open on the same
  thread.  Start and end come from ``clock``: a caller with a clock of its
  own (the serving engine's injectable one) passes it, and reads its
  stamps off the span instead of reading the clock again.
* :func:`record` appends a span that crosses threads (a request's queue
  wait) to the buffer only: a profiler annotation cannot cross threads.
* Every backend compile (``jax.monitoring``'s
  ``/jax/core/compile/backend_compile_duration`` event) is counted
  against the innermost span open on the compiling thread
  (:func:`compiles`), so an operator sees which step compiled.

Recording is always on.  With no profiler running the annotation costs
about a microsecond; the buffer holds the newest :data:`MAX_SPANS`
records and drops the oldest.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional, Union

import jax
from jax import monitoring

# holds a 30 s serving window at twice the span rate of one
# request a dispatch at 208 requests/s (tests/test_tracing.py)
MAX_SPANS = 1 << 17

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_buffer: collections.deque = collections.deque(maxlen=MAX_SPANS)
_lock = threading.Lock()
_ids = itertools.count(1)
_tls = threading.local()
_compiles: Dict[Optional[str], int] = {}
_ANY = object()


class Span:
    """One timed interval.  ``parent`` is the id of the span that caused
    it (None at a thread's top level); times are the ``clock``'s seconds."""

    __slots__ = ("name", "start", "end", "id", "parent", "thread", "attrs",
                 "_clock", "_annotation")

    def __init__(self, name: str, *,
                 clock: Callable[[], float] = time.perf_counter, **attrs):
        self.name, self.attrs, self._clock = name, attrs, clock
        self.start = self.end = None
        self.id, self.parent = next(_ids), None
        self.thread = None
        self._annotation = None

    @property
    def duration(self) -> float:
        return self.end - self.start

    def set(self, **attrs) -> None:
        """Add attributes known only once the span is open."""
        self.attrs.update(attrs)
        if self._annotation is not None and _profiling():
            self._annotation.set_metadata(**_plain(attrs))

    def __enter__(self) -> "Span":
        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.thread = threading.current_thread().name
        # the profiler is handed the attributes only while it records
        self._annotation = jax.profiler.TraceAnnotation(
            self.name, **(_plain(self.attrs) if _profiling() else {}))
        self._annotation.__enter__()
        self.start = self._clock()
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self.end = self._clock()
        _stack().pop()
        self._annotation.__exit__(*exc)
        self._annotation = None
        with _lock:
            _buffer.append(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"start={self.start}, end={self.end}, attrs={self.attrs})")


span = Span


def record(name: str, start: float, end: float, parent=None,
           **attrs) -> Span:
    """Append a finished span (buffer only); ``parent`` a Span or an id."""
    s = Span(name, **attrs)
    s.start, s.end = start, end
    s.parent = parent.id if isinstance(parent, Span) else parent
    s.thread = threading.current_thread().name
    with _lock:
        _buffer.append(s)
    return s


def spans(name: Optional[str] = None,
          parent: Union[Span, int, None, object] = _ANY) -> List[Span]:
    """Buffered spans, oldest first, of ``name`` (any when None) whose
    parent is ``parent`` (a Span, an id, or None for top-level spans;
    any when left out)."""
    if isinstance(parent, Span):
        parent = parent.id
    with _lock:
        out = list(_buffer)
    return [s for s in out if (name is None or s.name == name)
            and (parent is _ANY or s.parent == parent)]


def children(s: Span) -> List[Span]:
    return spans(parent=s)


def self_time(s: Span) -> float:
    """``s``'s duration less the part of it its children cover."""
    covered, reach = 0.0, s.start
    for a, b in sorted((max(c.start, s.start), min(c.end, s.end))
                       for c in children(s)):
        a = max(a, reach)
        if b > a:
            covered += b - a
            reach = b
    return s.duration - covered


def compiles() -> Dict[Optional[str], int]:
    """Backend compiles so far, by the innermost span open when each ran
    (None: outside any span)."""
    with _lock:
        return dict(_compiles)


def reset() -> None:
    """Drop every buffered span and compile count."""
    with _lock:
        _buffer.clear()
        _compiles.clear()


def _stack() -> List[Span]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


_profiling = jax.profiler.TraceAnnotation.is_enabled


def _plain(attrs: Dict) -> Dict:
    # the profiler takes str, int and float values
    return {k: v if isinstance(v, (str, int, float)) else str(v)
            for k, v in attrs.items()}


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _stack()
    key = stack[-1].name if stack else None
    with _lock:
        _compiles[key] = _compiles.get(key, 0) + 1


monitoring.register_event_duration_secs_listener(_on_duration)

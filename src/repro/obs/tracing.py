"""Spans: named intervals of the serving path on the host's monotonic clock.

A :class:`Span` is ``(name, start_ns, end_ns, span_id, parent_id, ref_id,
attrs)``: times from ``time.monotonic_ns()``; ``parent_id`` 0 for a root;
``ref_id`` the request or batch the span belongs to.  The taxonomy the
serving path records (DESIGN.md §10)::

    serve.request   submit -> ticket completed (request id)
      serve.queue   submit -> its batch dispatched (attrs: batch id)
    serve.wait      the last batch's end -> MicroBatcher.next_batch returns
                    the next batch (empty polls included; batch id)
    serve.batch     batch returned -> its last ticket completed (batch id;
                    attrs: n_real, padded rows, request ids, executor)
      engine.prepare  SearchEngine.search entered -> executor and arguments
      engine.launch   executor call -> it returns (attrs: executor, compiled)
      engine.record   registry on only: the engine's per-row work counts
      engine.wait     the server blocks once on the whole result
      serve.fetch     device -> host copies, per-request rows
      serve.complete  cache puts, ticket completions, counters

Finished spans go to one bounded in-memory ring per process (:data:`SPANS`):
fixed capacity, oldest dropped and counted, read out after a run by
:meth:`SpanRecorder.drain`.  Nothing is written while serving.

Off by default (:func:`enable` turns it on).  Off costs the caller one
checked ``SPANS.enabled`` per recording point: no allocation, no clock
read, no device sync.  On, every span opened with :meth:`SpanRecorder.open`
(the dispatch thread's) is also a ``jax.profiler.TraceAnnotation`` of the
same name, so a profile shows the program's spans above the device lanes;
spans recorded with :meth:`SpanRecorder.add` (the per-request ones, which
cross threads) are not.  Mapping ``monotonic_ns`` onto a profile's clock
is one offset (``bench/lib/trace.Recorder.offset_ns``).
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

DEFAULT_CAPACITY = 1 << 16


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: int          # 0: a root span
    ref_id: int             # the request or batch the span belongs to
    attrs: dict | None

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class SpanLog(NamedTuple):
    """What :meth:`SpanRecorder.drain` hands out: the ring's spans, oldest
    first, and how many older ones the ring dropped."""
    spans: list
    dropped: int


class _Open:
    """A span opened on one thread and not yet closed."""

    __slots__ = ("name", "start_ns", "span_id", "parent_id", "ref_id",
                 "attrs", "annotation")

    def __init__(self, name, start_ns, span_id, parent_id, ref_id, attrs,
                 annotation):
        self.name = name
        self.start_ns = start_ns
        self.span_id = span_id
        self.parent_id = parent_id
        self.ref_id = ref_id
        self.attrs = attrs
        self.annotation = annotation


class SpanRecorder:
    """The bounded ring finished spans go to, and each thread's stack of
    open ones (a span opened while another is open on the same thread is
    its child, and shares its ``ref_id`` unless given its own)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = False
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._dropped = 0
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._annotation = None     # jax.profiler.TraceAnnotation, if any

    def enable(self, on: bool = True) -> "SpanRecorder":
        if on and self._annotation is None:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation
            except ImportError:           # spans still go to the ring
                pass
        self.enabled = on
        return self

    def new_id(self) -> int:
        """A fresh id, unique in this process (span, request or batch)."""
        return next(self._ids)

    def add(self, name: str, start_ns: int, end_ns: int, *,
            span_id: int = 0, parent_id: int = 0, ref_id: int = 0,
            attrs: dict | None = None) -> int:
        """Record one finished span; returns its id."""
        span_id = span_id or next(self._ids)
        span = Span(name, start_ns, end_ns, span_id, parent_id, ref_id,
                    attrs)
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(span)
        return span_id

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def open(self, name: str, *, ref_id: int = 0, attrs: dict | None = None,
             start_ns: int | None = None) -> _Open:
        """Open a span on this thread, as a child of its innermost open
        span; close it with :meth:`close` on the same thread."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        annotation = None
        if self._annotation is not None:
            annotation = self._annotation(name)
            annotation.__enter__()
        sp = _Open(name,
                   time.monotonic_ns() if start_ns is None else start_ns,
                   next(self._ids), parent.span_id if parent else 0,
                   ref_id or (parent.ref_id if parent else 0), attrs,
                   annotation)
        stack.append(sp)
        return sp

    def close(self, sp: _Open, *, end_ns: int | None = None,
              ref_id: int = 0, **attrs) -> int:
        """Close ``sp`` and record it, with ``attrs`` added; spans opened
        inside it and left open (an exception unwound them) are dropped.
        Returns the end time."""
        end = time.monotonic_ns() if end_ns is None else end_ns
        stack = self._stack()
        while stack:
            top = stack.pop()
            if top.annotation is not None:
                top.annotation.__exit__(None, None, None)
            if top is sp:
                break
        if attrs:
            sp.attrs = {**(sp.attrs or {}), **attrs}
        self.add(sp.name, sp.start_ns, end, span_id=sp.span_id,
                 parent_id=sp.parent_id, ref_id=ref_id or sp.ref_id,
                 attrs=sp.attrs)
        return end

    def tag(self, **attrs) -> None:
        """Add ``attrs`` to this thread's innermost open span, if any."""
        stack = self._stack()
        if stack:
            top = stack[-1]
            top.attrs = {**(top.attrs or {}), **attrs}

    def drain(self) -> SpanLog:
        """Every span in the ring, oldest first, and the count dropped;
        empties the ring."""
        with self._lock:
            log = SpanLog(list(self._ring), self._dropped)
            self._ring.clear()
            self._dropped = 0
        return log


# The process recorder: the serving path records here.
SPANS = SpanRecorder()


def enable(on: bool = True) -> SpanRecorder:
    """Turn the process span recorder on (or off); returns it."""
    return SPANS.enable(on)

"""The program's spans and marks: host-clock records, mirrored into the
profiler trace.

``span(name, **attrs)`` times a block with ``time.monotonic()`` and enters
``jax.profiler.TraceAnnotation(name, **attrs)`` around it, so while a
profiler trace runs the span also shows on the host plane, on the device's
clock, beside the device's operations.  ``mark(name, **attrs)`` records an
instant (a zero-length annotation).  The annotation gets the scalar attrs;
the record keeps them all, and a span's attrs dict (yielded by ``span``)
may gain keys inside the block, kept in the record only.

Records go into bounded deques (the oldest drop first) that ``snapshot()``
reads and ``reset()`` clears.  Marks may come from the runtime's callback
threads, so appends and reads hold a lock; a span's parent is the span open
around it on the same thread.  Always on: a span costs two clock reads, an
annotation and an append.

Where the program records (one flush of ``launch/serve.AdaCURService``):

- ``serve.flush`` (attrs ``bucket``, ``n_real``; in the record also
  ``arrival_t``, the batch's hand-over times, and ``rounds``, the rounds
  the engine ran), with children ``serve.prepare``, ``engine.dispatch``
  (around the retriever's ``search``; child ``engine.tokenize``),
  ``serve.device_wait`` and ``serve.respond``, in that order;
- ``ce.round`` marks (attrs ``pairs``, ``pad``), one per CE scoring call the
  engine program runs, from ``DeviceCEScorer``'s counting callback.  A mark
  belongs to the flush whose dispatch-to-ready interval (``engine.dispatch``
  start to ``serve.device_wait`` end) holds it.

Outside a flush, ``kernels/flash_attention`` records a ``flash.schedule``
mark (attrs ``schedule``, ``pairs``, ``seq``, ``heads``) each time it is
traced: which schedule a compiled attention shape took.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterator, List, NamedTuple, Optional

import jax

# records kept of each kind: some 5,000 flushes' worth (six spans and up
# to n_rounds + 1 marks a flush)
MAX_RECORDS = 1 << 15


class Span(NamedTuple):
    name: str
    t0: float                  # time.monotonic() at entry
    t1: float                  # time.monotonic() at exit
    parent: Optional[int]      # id of the span open around it, same thread
    attrs: dict
    id: int


class Mark(NamedTuple):
    name: str
    t: float                   # time.monotonic()
    attrs: dict


class Records(NamedTuple):
    spans: List[Span]          # by start time, a parent before its children
    marks: List[Mark]          # by time


_lock = threading.Lock()
_spans: deque = deque(maxlen=MAX_RECORDS)
_marks: deque = deque(maxlen=MAX_RECORDS)
_ids = itertools.count()
_open = threading.local()      # .stack: ids of this thread's open spans


def _scalars(attrs: dict) -> dict:
    return {k: v for k, v in attrs.items() if isinstance(v, (bool, int, float, str))}


@contextmanager
def span(name: str, **attrs) -> Iterator[dict]:
    """Record the enclosed block as a span; yields its attrs dict."""
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    sid = next(_ids)
    parent = stack[-1] if stack else None
    stack.append(sid)
    t0 = time.monotonic()
    try:
        with jax.profiler.TraceAnnotation(name, **_scalars(attrs)):
            yield attrs
    finally:
        t1 = time.monotonic()
        stack.pop()
        with _lock:
            _spans.append(Span(name, t0, t1, parent, attrs, sid))


def mark(name: str, **attrs) -> None:
    """Record an instant."""
    t = time.monotonic()
    with jax.profiler.TraceAnnotation(name, **_scalars(attrs)):
        pass
    with _lock:
        _marks.append(Mark(name, t, attrs))


def snapshot() -> Records:
    """Copies of the records kept."""
    with _lock:
        spans, marks = list(_spans), list(_marks)
    spans.sort(key=lambda s: (s.t0, s.id))
    marks.sort(key=lambda m: m.t)
    return Records(spans, marks)


def reset() -> None:
    """Drop every record kept."""
    with _lock:
        _spans.clear()
        _marks.clear()

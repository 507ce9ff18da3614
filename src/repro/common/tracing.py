"""Host spans: named intervals of the server's own work, always recorded.

A span is one stretch of host work, timed on ``time.perf_counter``: its
name, start and end in seconds, the id of the span that was open around it
on the same thread (``parent``; None at the top of a thread), its
attributes, and its own ``id``. The last ``CAPACITY`` spans are kept in
memory, oldest dropped first; ``records()`` returns them and ``clear()``
forgets them. Recording costs about 2 us a span, so it is never switched
off.

Each ``span`` is also a ``jax.profiler.TraceAnnotation`` of the same name
and attributes: while a profiler session runs, the span appears on the
profile's host plane, on the same timeline as the device's ops, and shows
which host work a gap in device activity belongs to.

The serving loop records, per scheduler tick:

* ``scheduler.step``: the whole tick (admission, the runner's step,
  retirement);
* ``fno_runner.stage``: building the bucket's inputs (geomodel cache
  lookups, copies into the host batch, and where the runner keeps the
  static rows on the device, their assembly there); attributes
  ``resident_hits`` and ``resident_fills`` on that path: the static rows
  served from the runner's device table, and those uploaded into it;
* ``fno_runner.forward``: the jitted forward from its call until its
  output is a host array (upload, device compute, download); attribute
  ``bytes``, the host bytes this tick uploads (the host inputs and any
  table fill);
* ``fno_runner.feedback``: de-normalizing the outputs, and for rollouts
  the feedback into the next step's inputs;

and, per request, ``scheduler.queued``: submission to admission into a
slot, attribute ``rid``.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax

CAPACITY = 65_536


class Span(NamedTuple):
    name: str
    start: float            # time.perf_counter seconds
    end: float
    parent: Optional[int]   # id of the enclosing span on the same thread
    attrs: dict
    id: int


_records: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count()
_local = threading.local()


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the enclosed host work as span ``name`` with ``attrs``, also
    when it raises. Yields the span's attribute dict: what the body adds to
    it is recorded with the span (the profiler's event keeps only the
    attributes given at entry)."""
    stack = _open()
    parent = stack[-1] if stack else None
    sid = next(_ids)
    stack.append(sid)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield attrs
    finally:
        end = time.perf_counter()
        stack.pop()
        _records.append(Span(name, start, end, parent, attrs, sid))


def record(name: str, start: float, end: float, **attrs) -> None:
    """Record an interval whose ends are already known (``perf_counter``
    seconds), such as a request's wait in a queue. It has no parent and
    writes no profiler event."""
    _records.append(Span(name, start, end, None, attrs, next(_ids)))


def records() -> list:
    """The recorded spans, oldest first."""
    return list(_records)


def clear() -> None:
    _records.clear()

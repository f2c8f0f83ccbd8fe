"""The program's one span primitive: where the work happens, on the
profiler's clock.

``span(name, **attrs)`` stamps a start and an end on
``time.perf_counter()`` and appends one row

    ``(name, start, end, parent, attrs, sid)``

to ONE bounded process-wide ring when it closes (``parent`` is the
``sid`` of the span that was open on this thread when it began, ``None``
at the top).  For the same interval it holds a
``jax.profiler.TraceAnnotation(name, **attrs)``, so whenever a profiler
session is running the span is in the profiler's own trace, on the device
trace's clock, with no exporter: open the trace in Perfetto/XProf and the
host phases lie over the device operations.  With no session the
annotation is inert (TraceMe checks its flag before it formats anything).

The ring is always on, as the flight recorder is: no environment
variable, no constructor argument, no second buffer.  The cost with no
profiler running is two clock reads, one inert annotation and one tuple a
span.  Attributes are integers the caller already holds, never a sum made
for the span's sake and never a device value; those set after the span
opened (``sp.set(...)``, until it closes) reach the ring's row but not
the annotation, which is formatted when it is entered.

Every other span-like surface of the program is a user of this module and
reads no clock of its own for a span: ``profiler.RecordEvent``,
``obs.StepTimeline.phase``, the flight ring's step record and the request
tracer's batched step event in ``Engine.step``, and the compile spans of
``jit``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["RING_ROWS", "clock", "span", "mark", "snapshot"]

#: rows the ring keeps: a 50 s window plus set-up at a 10 ms step and
#: some ten spans a step, with room to spare (about 40 MB when full)
RING_ROWS = 1 << 17

_ring: deque = deque(maxlen=RING_ROWS)   # append is atomic under the GIL
_ids = itertools.count(1)                # so is next()
_local = threading.local()               # the open-span stack, per thread
#: the one clock of every span, and of what is stamped beside them
clock = time.perf_counter


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """One named interval.  A context manager; ``begin()`` / ``end()``
    do the same for callers that cannot nest a ``with``.  ``t0`` and
    ``t1`` are its stamps, for a caller that needs the time it already
    took (``Engine.step`` hands them to its metrics)."""

    __slots__ = ("name", "attrs", "t0", "t1", "sid", "parent", "_ann")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.t0 = self.t1 = self.sid = self.parent = self._ann = None

    def set(self, **attrs) -> None:
        """Attributes learned while the span is open."""
        self.attrs.update(attrs)

    def begin(self) -> "span":
        stack = _stack()
        self.parent = stack[-1].sid if stack else None
        self.sid = next(_ids)
        stack.append(self)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = clock()
        return self

    def end(self) -> None:
        if self.t1 is not None or self.t0 is None:
            return                       # closed twice, or never opened
        self.t1 = clock()
        self._ann.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:              # closed out of order
            stack.remove(self)
        _ring.append((self.name, self.t0, self.t1, self.parent,
                      self.attrs, self.sid))

    __enter__ = begin

    def __exit__(self, *_exc) -> bool:
        self.end()
        return False


def mark(name: str, **attrs) -> None:
    """A moment: a row whose end is its start, under the open span."""
    stack = _stack()
    with TraceAnnotation(name, **attrs):
        t = clock()
    _ring.append((name, t, t, stack[-1].sid if stack else None, attrs,
                  next(_ids)))


def snapshot(since: Optional[float] = None) -> List[tuple]:
    """The ring's rows, oldest first: every thread's, in the order they
    closed (a child before its parent).  ``since`` keeps the rows that
    ended at or after that ``perf_counter`` reading."""
    while True:
        try:
            rows = list(_ring)
            break
        except RuntimeError:             # another thread appended meanwhile
            continue
    if since is None:
        return rows
    return [r for r in rows if r[2] >= since]

"""Flight recorder: an always-on bounded ring of per-step summaries.

Shared by the serving engine (one per :class:`~..serving.engine.Engine`,
fed with slot/queue/block occupancy each scheduler step) and the
training runtime (one per
:class:`~..distributed.fault_tolerance.ResilientLoop`, fed with
step/loss/grad-norm/scale/snapshot-age from the divergence sentry's
single per-step report pull).  When something goes wrong — an engine
flips unhealthy, the fleet ejects a replica, the divergence sentry
escalates, the step watchdog fires — the ring is frozen into a **dump**:
the last N steps leading up to the failure, the post-mortem the
aggregate counters cannot reconstruct.

Recorders register themselves with :mod:`paddle_tpu.profiler` at
construction and surface through ``profiler.flight_record()``
(``serving_flight_record()`` remains as the serving-era alias); the
serving fleet additionally banks ejection dumps on the replica's
rebuild record, and training escalation attaches its dump to the raised
:class:`~..distributed.fault_tolerance.SentryEscalation`.
"""
from __future__ import annotations

import time
from collections import deque
from typing import List

from . import spans as _spans

__all__ = ["FlightRecorder"]


class FlightRecorder:
    """Always-on bounded ring of the last N step summaries.

    One per engine or training loop, fed a handful of host ints/floats
    per step (cost: one small dict append).  ``dump(reason)`` freezes
    the ring into a post-mortem record; dumps are kept (newest last, at
    most ``max_dumps``) and surfaced through
    ``profiler.flight_record()``.
    """

    def __init__(self, capacity: int = 256, name: str = "engine", *,
                 max_dumps: int = 4):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self.capacity = int(capacity)
        self.max_dumps = int(max_dumps)
        self._ring: deque = deque(maxlen=self.capacity)
        self.steps_seen = 0
        self.dumps: List[dict] = []
        from .. import profiler as _profiler

        _profiler._register_flight_recorder(self)

    def record(self, **fields) -> None:
        """Append one step summary (host ints/floats only — the caller
        is the scheduler/training loop, so this must stay
        allocation-light)."""
        self.steps_seen += 1
        fields["t"] = round(_spans.clock(), 6)
        self._ring.append(fields)

    def record_span(self, sp) -> None:
        """Append a CLOSED span's attributes as the step summary, stamped
        with the span's own end: the serving engine's per-step record is
        its ``engine.step`` span, so the ring holds the same dict the
        span ring's row does and no clock is read for it."""
        self.steps_seen += 1
        sp.attrs["t"] = round(sp.t1, 6)
        self._ring.append(sp.attrs)

    def dump(self, reason: str) -> dict:
        """Freeze the ring into a post-mortem record (newest events
        last) and bank it on ``dumps``.  Safe to call from the watchdog
        thread: the scheduler is stalled when the watchdog fires, so
        the ring is quiescent; a racing append at worst drops this
        dump's tail."""
        d = self.peek(reason)
        self.dumps.append(d)
        del self.dumps[:-self.max_dumps]
        return d

    def peek(self, reason: str) -> dict:
        """A dump-shaped view of the CURRENT ring WITHOUT banking it —
        the crash-dump path reads every live recorder this way so
        persisting artifacts never perturbs recorder state (a banked
        dump is an event consumers assert on; a crash capture must not
        manufacture one)."""
        try:
            events = [dict(e) for e in self._ring]
        except RuntimeError:             # ring mutated mid-copy
            events = []
        return {"name": self.name, "reason": reason,
                "wall_time": time.time(), "steps_seen": self.steps_seen,
                "events": events}

    def snapshot(self) -> dict:
        """JSON-ready view: ring occupancy plus every retained dump."""
        return {"name": self.name, "capacity": self.capacity,
                "steps_seen": self.steps_seen,
                "ring_depth": len(self._ring),
                "dumps": [dict(d, events=[dict(e) for e in d["events"]])
                          for d in self.dumps]}

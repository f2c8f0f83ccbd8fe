"""Step watchdog: turn a wedged step into a diagnosable restart.

A hung collective (peer died, link dropped, deadlocked host callback)
blocks the training thread forever — the process looks alive to the
launcher, so nothing relaunches it and the whole job wedges (reference:
fleet elastic treats "no heartbeat" the same way).

The watchdog is a daemon thread fed a heartbeat at every step boundary.
If no boundary is crossed within ``timeout`` seconds it:

1. dumps every thread's stack to stderr (the training thread's stack
   names the blocked call),
2. prints the last dispatched framework op (core.dispatch tracker) —
   for a stalled collective that is the op that never completed,
3. exits the process with ELASTIC_EXIT_CODE via ``os._exit`` so the
   launch/elastic restart path relaunches it — ``sys.exit`` from a
   non-main thread would only kill the watchdog itself.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import traceback
from typing import Callable, Optional

from ..fleet.elastic.manager import ELASTIC_EXIT_CODE

__all__ = ["StepWatchdog", "dump_all_stacks"]


def dump_all_stacks(file=None):
    """Write every live thread's current stack to ``file`` (stderr)."""
    file = file or sys.stderr
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in sys._current_frames().items():
        print(f"--- thread {names.get(ident, '?')} ({ident}) ---",
              file=file)
        for line in traceback.format_stack(frame):
            file.write(line)


class StepWatchdog:
    """Monitor thread that fires when no step boundary is crossed in time.

    ``notify(step)`` is the heartbeat; ``pause()`` suspends the deadline
    over legitimately-slow non-step phases (final checkpoint commit,
    evaluation) so they are not misread as hangs.
    """

    def __init__(self, timeout: float,
                 exit_code: int = ELASTIC_EXIT_CODE,
                 poll_interval: Optional[float] = None,
                 on_timeout: Optional[Callable[[], None]] = None,
                 hard_exit: bool = True,
                 startup_factor: float = 10.0):
        if timeout <= 0:
            raise ValueError("watchdog timeout must be > 0")
        self.timeout = float(timeout)
        self.exit_code = exit_code
        self.poll_interval = poll_interval or min(self.timeout / 4.0, 1.0)
        self.on_timeout = on_timeout
        self.hard_exit = hard_exit
        # the first step carries the cold XLA trace+compile, which can
        # legitimately dwarf a steady-state step — until one full step
        # boundary has been crossed, the deadline is timeout*startup_factor
        # (a compile slower than THAT is still caught, just later)
        self.startup_factor = float(startup_factor)
        self.last_step: Optional[int] = None
        self._boundaries = 0
        self.fired = False
        self._deadline_base = None          # None = paused
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._watch, name="paddle-tpu-step-watchdog", daemon=True)

    # -- lifecycle -------------------------------------------------------

    def start(self):
        with self._lock:
            self._deadline_base = time.monotonic()
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=self.poll_interval * 4)

    def request_stop(self):
        """Signal the monitor thread to exit without joining — safe to
        call from GC finalizers (join is not)."""
        self._stop.set()

    @property
    def alive(self) -> bool:
        """True while the monitor thread is still watching (it exits
        after firing once when ``hard_exit`` is off, and on stop)."""
        return self._thread.is_alive() and not self.fired

    # -- heartbeat -------------------------------------------------------

    def notify(self, step: int):
        with self._lock:
            if step != self.last_step:
                self._boundaries += 1
            self.last_step = step
            self._deadline_base = time.monotonic()

    def pause(self):
        with self._lock:
            self._deadline_base = None

    # -- monitor ---------------------------------------------------------

    def _watch(self):
        while not self._stop.wait(self.poll_interval):
            with self._lock:
                base = self._deadline_base
                warmed = self._boundaries >= 2   # one full step completed
            if base is None:
                continue
            deadline = self.timeout if warmed \
                else self.timeout * self.startup_factor
            stalled = time.monotonic() - base
            if stalled < deadline:
                continue
            self.fired = True
            self._report(stalled, deadline)
            if self.on_timeout is not None:
                self.on_timeout()
            if self.hard_exit:
                # the post-mortem must outlive the process os._exit is
                # about to kill: persist every flight ring and armed
                # trace to $PADDLE_TPU_TRACE_DIR (or the journal's
                # crash/ sibling) — best-effort, never blocks the exit
                try:
                    from ...obs.crashdump import persist_crash_artifacts

                    p = persist_crash_artifacts(
                        f"watchdog: no step boundary for "
                        f"{stalled:.1f}s (deadline {deadline:.1f}s)")
                    if p:
                        print(f"[watchdog] crash artifacts persisted "
                              f"to {p}", file=sys.stderr)
                except Exception:        # noqa: BLE001 — exiting anyway
                    pass
                sys.stderr.flush()
                sys.stdout.flush()
                os._exit(self.exit_code)
            return

    def _report(self, stalled: float, deadline: float):
        from ...core.dispatch import last_dispatched_op

        # notify() fires at the TOP of each step, so last_step is the
        # step that is hung mid-execution, not one that completed
        step = "during startup" if self.last_step is None \
            else f"in step {self.last_step}"
        print(f"[watchdog] no step boundary for {stalled:.1f}s "
              f"(deadline {deadline:.1f}s) — stalled {step}; "
              f"last dispatched op: {last_dispatched_op()!r}",
              file=sys.stderr)
        dump_all_stacks(sys.stderr)
        print(f"[watchdog] exiting with code {self.exit_code} for relaunch"
              if self.hard_exit else
              "[watchdog] hard_exit disabled; invoking on_timeout only",
              file=sys.stderr)

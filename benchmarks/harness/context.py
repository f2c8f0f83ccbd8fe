"""What a driver is handed for one run, and what it hands back."""
from __future__ import annotations

import dataclasses
import time
from typing import Optional


@dataclasses.dataclass
class RunContext:
    config: dict                 # the configuration file, as run
    mix: dict                    # the traffic mix / training job file
    limits: dict                 # the cell's limits on what ``correct`` compares
    seed: int
    seconds: float
    trace: bool
    chips: int = 1
    peaks: Optional[dict] = None     # None off the chip (CPU tests)
    out_dir: Optional[str] = None    # where a trace may be written
    t_start: float = dataclasses.field(default_factory=time.perf_counter)
    control: bool = False        # also read the lower-precision control
    #: a test's hook to break the timed path underneath: called with the
    #: driver's live objects before set-up drives them
    sabotage: Optional[object] = None

    def say(self, msg: str) -> None:
        print(msg, flush=True)


class Spans:
    """The benchmark's own host spans: ``(name, start, end, attrs)`` on
    ``time.perf_counter``, mirrored into the profiler's trace when one is
    being taken, so that idle gaps of the device can be named."""

    def __init__(self):
        self.rows = []

    def span(self, name: str, **attrs):
        return _Span(self, name, attrs)


class _Span:
    def __init__(self, owner, name, attrs):
        self.owner, self.name, self.attrs = owner, name, attrs
        self._ann = None

    def __enter__(self):
        import jax

        self._ann = jax.profiler.TraceAnnotation("bench." + self.name,
                                                 **self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.owner.rows.append((self.name, self.t0, t1, self.attrs))
        return False


class Checks:
    """Every number compared beside its limit; ``correct`` is their and."""

    def __init__(self, say):
        self.say, self.rows = say, []

    def _row(self, name, value, limit, ok, sign) -> bool:
        ok = bool(ok)                    # a nan compares false: not ok
        self.rows.append((name, float(value), float(limit), ok))
        self.say(f"CHECK {name}: {value:.6g} {sign} limit {limit:.6g} -> "
                 f"{'ok' if ok else 'FAIL'}")
        return ok

    def le(self, name: str, value: float, limit: float) -> bool:
        return self._row(name, value, limit, value <= limit, "<=")

    def ge(self, name: str, value: float, limit: float) -> bool:
        return self._row(name, value, limit, value >= limit, ">=")

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


def settle_heap() -> None:
    """Set-up's last act: collects what set-up left behind and moves every
    surviving object out of the collector's reach.  Tracing a model leaves
    millions of live Python objects; a full collection that walks them in the
    middle of a window stops the loop for a second or more."""
    import gc

    gc.collect()
    gc.freeze()


class GcWatch:
    """Times the collector's runs between ``start()`` and ``stop()``, so
    that a pause inside a window shows: ``pauses`` holds ``(generation,
    seconds)``."""

    def __init__(self):
        self.pauses, self._t = [], None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def start(self):
        import gc

        gc.callbacks.append(self._on)
        return self

    def stop(self) -> str:
        import gc

        gc.callbacks.remove(self._on)
        full = [s for g, s in self.pauses if g == 2]
        return (f"collector: {len(self.pauses)} runs, {len(full)} full, "
                f"longest {1e3 * max((s for _g, s in self.pauses), default=0):.1f} ms")


class HostWatch:
    """What the host did to the process between ``start()`` and ``stop()``:
    seconds the hypervisor kept from the machine's cores (``steal`` of
    ``/proc/stat``) and the times the process was switched off a core it
    still wanted, so that a stalled window names its cause."""

    @staticmethod
    def _read():
        import os
        import resource

        steal = None
        try:
            with open("/proc/stat") as f:
                steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            pass
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return steal, ru.ru_nivcsw, ru.ru_utime + ru.ru_stime

    def start(self):
        self._at = self._read()
        return self

    def stop(self) -> str:
        (s0, sw0, cpu0), (s1, sw1, cpu1) = self._at, self._read()
        steal = "unknown" if s0 is None or s1 is None else f"{s1 - s0:.2f} s"
        return (f"host: steal {steal} over all cores, {sw1 - sw0} involuntary "
                f"switches, process cpu {cpu1 - cpu0:.1f} s")


class CompileCounter:
    """Counts backend compiles through JAX's own monitoring events, so that
    a compile inside the measured window shows."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.count, self.seconds = 0, 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

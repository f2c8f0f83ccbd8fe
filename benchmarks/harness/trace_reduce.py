"""From a profiler trace to numbers: device busy and idle time, a kernel's
summed device time, the operations that took most time, and the longest idle
gaps named by the benchmark's own host span open at the time.

Kept with the benchmark so that every PR reduces a trace the same way.  The
arithmetic works on plain tuples (``Trace``), so tests feed it hand-built
traces; ``read_xplane`` fills a ``Trace`` from the ``.xplane.pb`` the JAX
profiler writes.

What the trace of a TPU v5e looks like (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text
(``%jvp_attention.pallas_flash_.47 = (bf16[256,1024,64]...) custom-call(...)``:
a Pallas kernel is a ``custom-call`` that carries its ``jax.named_scope``), the
line ``XLA Modules`` one event per executed program (``jit_program(<hash>)``
for a ``jit.to_static`` step), and the plane ``/host:CPU`` whose thread lines hold the
``bench.*`` annotations on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
SLICE = "slice"          # the span that brackets what a reduction may read
SETTLE_S = 1.5           # from the profiler's start to the slice's start
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    #: per device plane: ``(start_s, end_s, name, text)``; ``text`` is the
    #: name plus every string stat, what a kernel's pattern is matched on
    device_ops: Dict[str, List[Tuple[float, float, str, str]]]
    #: ``(name, start_s, end_s, attrs)`` of the benchmark's host spans;
    #: ``attrs`` are the numbers the span was opened with
    host_spans: List[Tuple[str, float, float, dict]]
    #: per device plane: ``(start_s, end_s, name)`` of whole executed
    #: programs (the ``XLA Modules`` line)
    modules: Dict[str, List[Tuple[float, float, str]]] = dataclasses.field(
        default_factory=dict)

    def window(self) -> Tuple[float, float]:
        """The traced slice: first to last thing the trace holds."""
        starts = [o[0] for ops in self.device_ops.values() for o in ops] \
            + [s[1] for s in self.host_spans]
        ends = [o[1] for ops in self.device_ops.values() for o in ops] \
            + [s[2] for s in self.host_spans]
        if not starts:
            return (0.0, 0.0)
        return (min(starts), max(ends))


def short_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.12 = f32[...] fusion(...)``); the instruction's own name is
    enough to tell operations apart."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:120]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_spans, modules = {}, [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            rows = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = sorted(
                        (e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, e.name)
                        for e in line.events)
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    text = " ".join([e.name] + [
                        str(v) for _k, v in e.stats if isinstance(v, str)])
                    t0 = e.start_ns * 1e-9
                    rows.append((t0, t0 + e.duration_ns * 1e-9,
                                 short_name(e.name), text))
            rows.sort()
            device_ops[plane.name] = rows
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        t0 = e.start_ns * 1e-9
                        host_spans.append((e.name[len(SPAN_PREFIX):], t0,
                                           t0 + e.duration_ns * 1e-9,
                                           dict(e.stats)))
    host_spans.sort(key=lambda s: s[1])
    return cut_to_slice(Trace(device_ops, host_spans, modules))


def cut_to_slice(trace: Trace) -> Trace:
    """The trace inside its ``slice`` span: device operations and programs
    that lie wholly inside, host spans clipped to it.  The profiler's start
    stalls the device for about a second and its stop stalls the host for
    several: the ``Profiler`` opens the slice once the start has settled and
    closes it before the stop, so that neither is read as the program's idle
    time.  A trace without the span is left whole."""
    found = [s for s in trace.host_spans if s[0] == SLICE]
    if not found:
        return trace
    t0, t1 = found[0][1], found[0][2]

    def inside(rows):
        return [r for r in rows if t0 <= r[0] and r[1] <= t1]

    spans = [(n, max(s, t0), min(e, t1), a) for n, s, e, a in trace.host_spans
             if s < t1 and e > t0 and n != SLICE]
    return Trace({k: inside(v) for k, v in trace.device_ops.items()},
                 spans + [(SLICE, t0, t0, {}), (SLICE, t1, t1, {})],
                 {k: inside(v) for k, v in trace.modules.items()})


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    if not trace.device_ops:
        return 0.0
    per = [sum(e - s for s, e in union((o[0], o[1]) for o in ops))
           for ops in trace.device_ops.values()]
    return sum(per) / len(per)


def busy_within(trace: Trace, t0: float, t1: float) -> float:
    """Busy seconds of the first device plane inside ``[t0, t1]``."""
    if not trace.device_ops:
        return 0.0
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    return sum(min(e, t1) - max(s, t0)
               for s, e in union((o[0], o[1]) for o in ops)
               if e > t0 and s < t1)


def main_program_runs(trace: Trace) -> List[Tuple[float, float]]:
    """``(start, end)`` of every whole execution, on the first device plane,
    of the program that took most device time in the slice."""
    if not trace.modules:
        return []
    mods = trace.modules[sorted(trace.modules)[0]]
    total: Dict[str, float] = {}
    for s, e, name in mods:
        total[name] = total.get(name, 0.0) + (e - s)
    if not total:
        return []
    main = max(total, key=total.get)
    return [(s, e) for s, e, name in mods if name == main]


def idle_share(trace: Trace) -> float:
    t0, t1 = trace.window()
    return 1.0 - busy_seconds(trace) / (t1 - t0) if t1 > t0 else 0.0


def kernel_events(trace: Trace, patterns) -> List[Tuple[float, float, str]]:
    """Device events (first plane) whose text matches any of ``patterns``."""
    regs = [re.compile(p) for p in patterns]
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    return [(o[0], o[1], o[2]) for o in ops
            if any(r.search(o[3]) for r in regs)]


def kernel_seconds(trace: Trace, patterns) -> Tuple[float, int]:
    ev = kernel_events(trace, patterns)
    return (sum(e - s for s, e, _n in ev), len(ev))


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """``[[name, seconds], ...]``: operations by summed device time (first
    plane), under the names the trace prints."""
    if not trace.device_ops:
        return []
    total: Dict[str, float] = {}
    for s, e, name, _t in trace.device_ops[sorted(trace.device_ops)[0]]:
        total[name] = total.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def span_at(trace: Trace, t: float) -> str:
    """The innermost benchmark span open at ``t`` (latest started)."""
    best = None
    for name, s, e, _attrs in trace.host_spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "no_span"


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """``[[span name, seconds], ...]``: idle time of the first device plane
    inside the traced slice, summed by the host span open at each gap's
    middle, longest first."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    t0, t1 = trace.window()
    busy = union((o[0], o[1]) for o in ops)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    total: Dict[str, float] = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            label = span_at(trace, (a + b) / 2)
            total[label] = total.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def summary(trace: Trace, n: int = 40) -> dict:
    """What a person looks at once to learn the event names: the heaviest
    operations with the text a pattern would be matched on."""
    out = {"planes": {k: len(v) for k, v in trace.device_ops.items()},
           "modules": {k: sorted({m[2] for m in v})[:20]
                       for k, v in trace.modules.items()},
           "host_spans": len(trace.host_spans), "window": trace.window(),
           "ops": []}
    if trace.device_ops:
        agg: Dict[str, list] = {}
        for s, e, name, text in trace.device_ops[sorted(trace.device_ops)[0]]:
            a = agg.setdefault(name, [0.0, 0, text])
            a[0] += e - s
            a[1] += 1
        ranked = sorted(agg.items(), key=lambda kv: -kv[1][0])
        kernels = [kv for kv in ranked[n:] if "custom-call" in kv[1][2]][:24]
        out["ops"] = [{"name": k, "seconds": v[0], "count": v[1],
                       "text": v[2][:600]} for k, v in ranked[:n] + kernels]
    return out


class Profiler:
    """Traces one slice of a window: ``maybe(now)`` starts the profiler once
    ``start_after`` seconds have passed, opens the ``slice`` span
    ``SETTLE_S`` after the start has returned, and ``length`` seconds later
    closes it and stops the profiler; ``finish()`` stops it if still on and
    returns the ``Trace``, cut to the slice."""

    def __init__(self, out_dir: str, t0: float, start_after: float,
                 length: float):
        self.dir = os.path.join(out_dir, "trace")
        self.t_on, self.length = t0 + start_after, length
        self.state, self._slice = "waiting", None

    def maybe(self, now: float) -> None:
        import jax

        if self.state == "waiting" and now >= self.t_on:
            import shutil
            import time

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_slice = time.perf_counter() + SETTLE_S
            self.state = "settling"
        elif self.state == "settling" and now >= self.t_slice:
            self._slice = jax.profiler.TraceAnnotation(SPAN_PREFIX + SLICE)
            self._slice.__enter__()
            self.t_off = now + self.length
            self.state = "on"
        elif self.state == "on" and now >= self.t_off:
            self._stop()

    def _stop(self) -> None:
        import jax

        if self._slice is not None:
            self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def finish(self):
        if self.state in ("settling", "on"):
            self._stop()
        if self.state != "done":
            return None
        import shutil

        trace = read_xplane(find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)   # traces are large
        return trace

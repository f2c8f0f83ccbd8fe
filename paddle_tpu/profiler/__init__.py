"""Profiler: schedule-driven tracing + statistics for TPU programs.

Reference parity: ``python/paddle/profiler/profiler.py:271`` (``Profiler``
with CLOSED/READY/RECORD(+RETURN) state machine, ``make_scheduler:71``,
``export_chrome_tracing:158``) and ``profiler_statistic.py`` (summary
tables).  TPU-first design: the capture engine is ``jax.profiler``
(TraceMe/XPlane; captures both host spans and device (TPU) activity via
PJRT), so this layer owns exactly what SURVEY §5.1 says must be rebuilt —
the schedule/state machine, span annotation API, and the statistics
aggregation — not the tracer itself.

Usage (mirrors the reference)::

    import paddle_tpu.profiler as profiler
    p = profiler.Profiler(
        scheduler=profiler.make_scheduler(closed=1, ready=1, record=4),
        on_trace_ready=profiler.export_chrome_tracing("./log"))
    p.start()
    for it, batch in enumerate(loader()):
        train_step(batch)
        p.step()
    p.stop()
    p.summary()
"""
from __future__ import annotations

import enum
import glob
import gzip
import json
import os
import time
from typing import Callable, Iterable, Optional, Union

from .statistic import StatisticData, SortedKeys  # noqa: F401
from .timer import benchmark  # noqa: F401

# -- serving metrics export -------------------------------------------------
# Live serving.Engine instances register their ServingMetrics here (weakly:
# an engine going away must not leak through the profiler); serving_stats()
# is the process-wide /stats aggregation point.
import weakref as _weakref

_serving_metrics: "list" = []


def _register_serving_metrics(m) -> None:
    _serving_metrics.append(_weakref.ref(m))


def _live_serving_metrics():
    """Dereference the registry, pruning entries whose engine is gone."""
    out, live = [], []
    for ref in _serving_metrics:
        m = ref()
        if m is None:
            continue
        live.append(ref)
        out.append(m)
    _serving_metrics[:] = live
    return out


def serving_stats() -> dict:
    """Snapshot of every live serving engine's metrics, keyed by engine
    name (TTFT, inter-token latency, tokens/sec, queue depth, slot
    occupancy, compile-cache hits/misses, failure/retry counters, and the
    engine health snapshot — see serving.ServingMetrics)."""
    return {m.name: m.snapshot() for m in _live_serving_metrics()}


def serving_health() -> dict:
    """Liveness-only view over every live engine, keyed by engine name:
    state (active/draining/stopped/unhealthy), last-step age, consecutive
    compiled-step failures, queue depth, free slots.  The cheap probe a
    load balancer polls — no latency distributions are computed."""
    return {m.name: m.health_cb() for m in _live_serving_metrics()
            if m.health_cb is not None}


def serving_paging() -> dict:
    """Paged-KV observability across every live engine, keyed by engine
    name: block-pool occupancy (free/used/cached), eviction and
    copy-on-extend counters, and prefix-cache hit rates."""
    out = {}
    for m in _live_serving_metrics():
        p = m._paging_section()
        if p is not None:
            out[m.name] = p
    return out


_fleet_metrics: "list" = []


def _register_fleet_metrics(m) -> None:
    _fleet_metrics.append(_weakref.ref(m))


# -- training observatory (ISSUE 13) ----------------------------------------
# ResilientLoop registers itself here (weakly) at construction; its
# train_stats() snapshot carries the step-timeline counters, the compile
# ledger, and the sentry/rollback counters.

_train_stats: "list" = []


def _register_train_stats(obj) -> None:
    _train_stats.append(_weakref.ref(obj))


def train_stats() -> dict:
    """Snapshot of every live training loop's observatory
    (step-timeline counters, compile ledger — ``["compiles"]`` — and
    divergence-sentry/rollback counters), keyed by loop name (suffixed
    ``#2``... when several loops share one).  The training analog of
    :func:`serving_stats`; flattened into the process-wide metrics
    exposition by ``obs.render_all_metrics``."""
    out, live = {}, []
    for ref in _train_stats:
        o = ref()
        if o is None:
            continue
        live.append(ref)
        snap = o.train_stats()
        name = snap.get("name", "training")
        key, i = name, 1
        while key in out:
            i += 1
            key = f"{name}#{i}"
        out[key] = snap
    _train_stats[:] = live
    return out


_flight_recorders: "list" = []


def _register_flight_recorder(r) -> None:
    _flight_recorders.append(_weakref.ref(r))


def flight_record() -> dict:
    """Flight-recorder surface (ISSUE 9, generalized in ISSUE 12): for
    every live recorder — serving engines AND training loops (the
    ``"training"`` ring ``ResilientLoop`` feeds) — the bounded ring of
    recent step summaries plus any post-mortem dumps frozen when
    ``health()`` flipped unhealthy, the fleet ejected the replica, the
    divergence sentry escalated, or the step watchdog fired.  Keyed by
    recorder name; an ejected-and-rebuilt replica's generations share
    its name, and the fleet's banked ejection dumps
    (``FleetMetrics.flight_cb``) are merged in so a dump survives its
    engine being discarded.  Returns
    ``{name: [snapshot_or_dump, ...]}`` (newest last)."""
    out: dict = {}
    seen_dumps = set()
    live = []
    for ref in _flight_recorders:
        rec = ref()
        if rec is None:
            continue
        live.append(ref)
        snap = rec.snapshot()
        for d in rec.dumps:
            seen_dumps.add(id(d))
        out.setdefault(rec.name, []).append(snap)
    _flight_recorders[:] = live
    for ref in _fleet_metrics:
        m = ref()
        if m is None or getattr(m, "flight_cb", None) is None:
            continue
        for name, dumps in m.flight_cb().items():
            for d in dumps:
                if id(d) not in seen_dumps:
                    out.setdefault(name, []).append(
                        {"name": name, "banked": True, "dumps": [d]})
    return out


#: serving-era alias for :func:`flight_record` (pre-ISSUE-12 name; the
#: registry has always been recorder-agnostic)
serving_flight_record = flight_record


def serving_fleet() -> dict:
    """Supervision snapshot of every live serving fleet, keyed by fleet
    name: per-replica occupancy/state table, dispatch + prefix-affinity
    hit rate, ejection/rebuild counters with measured failover recovery
    time, and request redispatches — see serving.FleetMetrics."""
    out, live = {}, []
    for ref in _fleet_metrics:
        m = ref()
        if m is None:
            continue
        live.append(ref)
        out[m.name] = m.snapshot()
    _fleet_metrics[:] = live
    return out


class ProfilerState(enum.Enum):
    """Reference: profiler.py ProfilerState (:34)."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # the last RECORD step of a cycle


class ProfilerTarget(enum.Enum):
    """What to capture.  On this stack CPU (host TraceMe spans) and TPU
    (device activity via PJRT) are captured together by jax.profiler;
    GPUs are out of scope."""
    CPU = 0
    TPU = 1


def make_scheduler(*, closed: int, ready: int, record: int,
                   repeat: int = 0, skip_first: int = 0
                   ) -> Callable[[int], ProfilerState]:
    """Build a step→state schedule: ``skip_first`` steps CLOSED, then cycles
    of [closed, ready, record] repeated ``repeat`` times (0 = forever).
    Reference: profiler.py:71."""
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("closed/ready must be >=0 and record >=1")
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def _default_state_fn(_step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """on_trace_ready callback: leave the chrome trace produced by the
    capture in ``dir_name`` and remember its path on the profiler.
    Reference: profiler.py:158."""

    def handle(prof: "Profiler") -> None:
        prof._exported_chrome_trace = prof._find_chrome_trace()

    handle._dir_name = dir_name  # type: ignore[attr-defined]
    return handle


def export_protobuf(dir_name: str, worker_name: Optional[str] = None
                    ) -> Callable:
    """on_trace_ready callback for the XPlane protobuf (TensorBoard's
    native input); jax.profiler always writes it — this just records where."""

    def handle(prof: "Profiler") -> None:
        pats = os.path.join(prof._log_dir, "plugins", "profile", "*", "*.xplane.pb")
        hits = sorted(glob.glob(pats))
        prof._exported_protobuf = hits[-1] if hits else None

    handle._dir_name = dir_name  # type: ignore[attr-defined]
    return handle


class RecordEvent:
    """User-annotated span, visible in the trace and the statistics tables.
    Reference: paddle.profiler.RecordEvent / platform::RecordEvent
    (event_tracing.h) — here a span of :mod:`paddle_tpu.obs.spans`: a
    ``jax.profiler.TraceAnnotation`` for the interval, and a row in the
    program's span ring beside the engine's and the compiler's own."""

    def __init__(self, name: str, event_type: Optional[str] = None):
        self.name = name
        self._span = None

    def begin(self):
        from ..obs import spans

        self._span = spans.span(self.name).begin()

    def end(self):
        if self._span is not None:
            self._span.end()
            self._span = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """Schedule-driven profiler over jax.profiler.

    State machine per reference profiler.py:271: each ``step()`` call
    advances the step counter and applies the scheduler's target state —
    starting the capture on CLOSED→{READY,RECORD} transitions and stopping
    (+ invoking ``on_trace_ready``) when leaving RECORD_AND_RETURN.  READY
    runs the tracer but drops the result (warmup).  ``timer_only=True``
    skips tracing and only collects step timing (ips) like the reference's
    benchmark timer."""

    def __init__(self,
                 *,
                 targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler: Union[Callable, tuple, None] = None,
                 on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False,
                 log_dir: Optional[str] = None):
        if isinstance(scheduler, (tuple, list)):  # (start, end) sugar
            start, end = scheduler
            scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                       record=end - start, repeat=1)
        self._state_fn = scheduler or _default_state_fn
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._log_dir = log_dir or getattr(on_trace_ready, "_dir_name", None) \
            or "./profiler_log"
        self.current_state = ProfilerState.CLOSED
        self._step = 0
        self._tracing = False
        self._capture_is_warmup = False
        self._exported_chrome_trace: Optional[str] = None
        self._exported_protobuf: Optional[str] = None
        self._step_times: list = []
        self._t_last: Optional[float] = None

    # -- capture engine -----------------------------------------------------
    def _start_trace(self, warmup: bool) -> None:
        if self._timer_only or self._tracing:
            return
        import jax

        os.makedirs(self._log_dir, exist_ok=True)
        jax.profiler.start_trace(self._log_dir)
        self._tracing = True
        self._capture_is_warmup = warmup

    def _stop_trace(self, ready: bool) -> None:
        if not self._tracing:
            return
        import jax

        jax.profiler.stop_trace()
        self._tracing = False
        if ready and not self._capture_is_warmup:
            if self._on_trace_ready is not None:
                self._on_trace_ready(self)
            else:
                self._exported_chrome_trace = self._find_chrome_trace()

    def _find_chrome_trace(self) -> Optional[str]:
        hits = sorted(glob.glob(os.path.join(
            self._log_dir, "plugins", "profile", "*", "*.trace.json.gz")))
        return hits[-1] if hits else None

    # -- state machine ------------------------------------------------------
    def _transit(self, new: ProfilerState) -> None:
        old = self.current_state
        if old == new:
            return
        rec = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if old == ProfilerState.CLOSED and new != ProfilerState.CLOSED:
            self._start_trace(warmup=(new == ProfilerState.READY))
        elif old == ProfilerState.READY and new in rec:
            # warmup capture becomes the real one: restart for clean data
            self._stop_trace(ready=False)
            self._start_trace(warmup=False)
        elif old in rec and new == ProfilerState.CLOSED:
            self._stop_trace(ready=True)
        elif old in rec and new == ProfilerState.READY:
            self._stop_trace(ready=True)
            self._start_trace(warmup=True)
        self.current_state = new

    def start(self) -> "Profiler":
        self._t_last = time.perf_counter()
        self._transit(self._state_fn(self._step))
        return self

    def step(self, num_samples: Optional[int] = None) -> None:
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append((now - self._t_last, num_samples))
        self._t_last = now
        # leaving RECORD_AND_RETURN finalizes the cycle even if the next
        # scheduled state is also a recording one
        if self.current_state == ProfilerState.RECORD_AND_RETURN:
            self._stop_trace(ready=True)
            self.current_state = ProfilerState.CLOSED
        self._step += 1
        self._transit(self._state_fn(self._step))

    def stop(self) -> None:
        self._stop_trace(ready=self.current_state in
                         (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN))
        self.current_state = ProfilerState.CLOSED

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results ------------------------------------------------------------
    @property
    def chrome_trace_path(self) -> Optional[str]:
        return self._exported_chrome_trace

    def statistic_data(self) -> Optional[StatisticData]:
        path = self._exported_chrome_trace or self._find_chrome_trace()
        if path is None:
            return None
        return load_profiler_result(path)

    def summary(self, sorted_by: SortedKeys = SortedKeys.DeviceTotal,
                op_detail: bool = True, thread_sep: bool = False,
                time_unit: str = "ms", row_limit: int = 20) -> str:
        """Print + return the statistics tables (reference
        profiler_statistic.py summary)."""
        data = self.statistic_data()
        lines = []
        if self._step_times:
            ts = [t for t, _ in self._step_times[1:]] or \
                [t for t, _ in self._step_times]
            avg = sum(ts) / len(ts)
            lines.append(f"steps: {len(self._step_times)}  "
                         f"avg step: {avg * 1e3:.2f} ms")
            ns = [n for _, n in self._step_times if n]
            if ns:
                lines.append(f"ips: {sum(ns) / sum(t for t, n in self._step_times if n):.2f} samples/s")
        if data is not None:
            lines.append(data.format_tables(sorted_by=sorted_by,
                                            row_limit=row_limit,
                                            time_unit=time_unit))
        out = "\n".join(lines)
        print(out)
        return out


def load_profiler_result(path: str) -> StatisticData:
    """Parse an exported chrome trace (``*.trace.json.gz`` or ``.json``)
    into a StatisticData.  Reference: profiler.py load_profiler_result."""
    if path.endswith(".gz"):
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
    else:
        with open(path) as f:
            trace = json.load(f)
    return StatisticData.from_chrome_trace(trace)

"""paddle_tpu.obs — observability for the serving AND training stacks.

A thin, dependency-free export layer over
:class:`paddle_tpu.serving.tracing.RequestTracer` and the
``Engine.stats()`` / ``Fleet.stats()`` snapshots:

- :func:`chrome_trace` / :func:`write_chrome_trace` — Chrome/Perfetto
  trace-event JSON (load in https://ui.perfetto.dev or
  ``chrome://tracing``): one track group (process) per replica, one
  thread per slot plus a scheduler track, spans as complete events,
  preempt/redispatch links as flow arrows, per-step batch occupancy as
  a counter track;
- :func:`write_jsonl` / :func:`jsonl_lines` — one JSON object per
  event, wall-clock timestamps added AT EXPORT from the tracer's
  anchor pair (events themselves are stamped monotonically and never
  do wall-clock math);
- :func:`render_metrics` / :func:`render_all_metrics` — Prometheus-
  style text exposition of the existing ``stats()`` snapshots (no new
  counters: this is the same dict, flattened for scrapers).

Everything here is host-side and read-only: exporting never touches an
engine, a traced value, or a compiled program.

Underneath them all sits :mod:`~.spans`, the one span primitive: an
always-on bounded ring of ``(name, start, end, parent, attrs, sid)`` rows on
``time.perf_counter()``, each span also a ``jax.profiler.TraceAnnotation``
(the phases of ``Engine.step``, ``jit``'s compile spans, ``RecordEvent``,
``StepTimeline.phase``) — docs/OBSERVABILITY.md "The span primitive".

:class:`~.flight.FlightRecorder` also lives here — the always-on
bounded step-summary ring both the serving engine and the training
runtime feed (frozen into a post-mortem dump on unhealthy/eject/
sentry-escalation/watchdog events).

The **training step observatory** (ISSUE 13) lives here too:

- :class:`~.train.StepTimeline` / :func:`~.train.validate_timeline` —
  host-side per-step spans (data fetch, dispatch, device wait,
  snapshot/checkpoint, sentry rollback/skip), rendered by the SAME
  Perfetto/JSONL exporters (process ``trainer``, one thread per phase,
  rollbacks as flow arrows);
- :class:`~.compile_ledger.CompileLedger` — every executable-cache
  miss recorded with cache key, wall seconds, arg specs, and call
  site, so a steady-state recompile is a named anomaly;
- :class:`~.hlo_cost.CostLedger` — XLA cost analysis per compiled
  program (flops, bytes, HLO op mix, analytic roofline MFU) plus the
  stable schedule fingerprint — the CPU-verifiable surface the
  compute/collective-overlap work will be asserted on.
"""
from .flight import FlightRecorder  # noqa: F401
from .perfetto import chrome_trace, write_chrome_trace  # noqa: F401
from .jsonl import jsonl_lines, write_jsonl  # noqa: F401
from .metrics import render_metrics, render_all_metrics  # noqa: F401
from .train import (NULL_TIMELINE, StepTimeline,  # noqa: F401
                    validate_timeline)
from .compile_ledger import CompileLedger  # noqa: F401
from .hlo_cost import CostLedger, scope_maps  # noqa: F401

__all__ = ["FlightRecorder", "chrome_trace", "write_chrome_trace",
           "jsonl_lines", "write_jsonl", "render_metrics",
           "render_all_metrics", "validate_trace", "StepTimeline",
           "NULL_TIMELINE", "validate_timeline", "CompileLedger",
           "CostLedger", "scope_maps"]


def __getattr__(name):
    # lazy: serving.tracing imports obs.flight at module top, so an
    # eager import here would be circular (obs partially initialized
    # when tracing asks back for it)
    if name == "validate_trace":
        from ..serving.tracing import validate_trace

        return validate_trace
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

"""Compile ledger — every XLA compile, named, timed, and attributed.

The zero-recompile discipline is this repo's core performance invariant
(docs/SERVING.md, docs/ANALYSIS.md): after warmup, a steady-state
executable-cache miss is a *bug* that costs seconds of wall time per
occurrence.  The serving engine already counts misses
(``stats()["compile_cache"]``); training, until now, could only see
them as unexplained step-time spikes.

:class:`CompileLedger` subscribes to the executable-cache miss path
(:func:`paddle_tpu.jit.subscribe_compiles`) and records **every**
compile as a structured record:

====================  ======================================================
``fn``                qualname of the compiled function
``key``               short digest of the full cache key (spec + mode bits)
``arg_specs``         ``dtype[shape]`` list of the tensor arguments
``seconds``           wall time: trace + build + the first call (jax.jit
                      compiles lazily, so the first execution pays XLA)
``site``              attributed call site (innermost non-framework frame)
``executed``          False for trace-only discovery
                      (``get_concrete_program`` — no executable built)
``steady_state``      True when the miss happened after
                      :meth:`CompileLedger.mark_steady` — a named anomaly
====================  ======================================================

so cumulative compile time is a first-class metric
(``stats()["compiles"]``, surfaced through ``profiler.train_stats()``)
and a steady-state miss is a *named* event — function, shapes, call
site — instead of a silent latency cliff.

Pure host-side bookkeeping: attaching a ledger changes no cache key and
performs no device transfer; with no ledger attached the miss path pays
one falsy check.
"""
from __future__ import annotations

from typing import Dict, List, Optional

__all__ = ["CompileLedger"]


class CompileLedger:
    """Subscriber-side ledger of executable-cache misses.

    Use as a context manager or with explicit
    :meth:`attach`/:meth:`detach`::

        ledger = CompileLedger()
        with ledger:
            warmup()              # recorded, pre-steady
            ledger.mark_steady()  # everything after this is an anomaly
            train(...)
        assert ledger.steady_state_misses == 0

    Args:
        name: ledger label (the ``profiler.train_stats()`` key context).
        max_records: retention bound; past it records are dropped and
            counted (the counters keep counting).
    """

    def __init__(self, name: str = "train", max_records: int = 4096):
        if max_records < 1:
            raise ValueError(f"max_records must be >= 1, got {max_records}")
        self.name = name
        self.max_records = int(max_records)
        self.records: List[dict] = []
        self.dropped = 0
        self.compiles = 0
        self.total_seconds = 0.0
        self.steady_state_misses = 0
        self._steady = False
        self._attached = False

    # -- subscription -------------------------------------------------------

    def attach(self) -> "CompileLedger":
        if not self._attached:
            from ..jit import subscribe_compiles

            subscribe_compiles(self._on_compile)
            self._attached = True
        return self

    def detach(self) -> None:
        if self._attached:
            from ..jit import unsubscribe_compiles

            unsubscribe_compiles(self._on_compile)
            self._attached = False

    def __enter__(self) -> "CompileLedger":
        return self.attach()

    def __exit__(self, *_exc) -> bool:
        self.detach()
        return False

    # -- recording ----------------------------------------------------------

    def _on_compile(self, record: dict) -> None:
        self.compiles += 1
        self.total_seconds += record["seconds"]
        rec = dict(record, steady_state=self._steady)
        if self._steady:
            self.steady_state_misses += 1
        if len(self.records) >= self.max_records:
            self.dropped += 1
            return
        self.records.append(rec)

    def mark_steady(self) -> None:
        """Everything compiled from here on is a steady-state miss — a
        named anomaly.  The training loops call this after the first
        completed step (by then every program of a fixed-shape step has
        been built); call it after ``warmup()`` when driving manually."""
        self._steady = True

    @property
    def steady(self) -> bool:
        return self._steady

    # -- introspection ------------------------------------------------------

    def anomalies(self) -> List[dict]:
        """The steady-state miss records — each one names the function,
        arg specs, and call site that recompiled when nothing should."""
        return [r for r in self.records if r.get("steady_state")]

    def stats(self) -> dict:
        """JSON-ready counters (``profiler.train_stats()`` surface).
        ``by_function`` aggregates count/seconds per compiled function;
        steady-state anomalies ride along fully named."""
        by_fn: Dict[str, dict] = {}
        for r in self.records:
            agg = by_fn.setdefault(r["fn"], {"count": 0, "seconds": 0.0})
            agg["count"] += 1
            agg["seconds"] = round(agg["seconds"] + r["seconds"], 6)
        return {
            "compiles": self.compiles,
            "total_seconds": round(self.total_seconds, 6),
            "steady_state_misses": self.steady_state_misses,
            "records_dropped": self.dropped,
            "by_function": by_fn,
            "anomalies": [
                {k: r[k] for k in ("fn", "key", "arg_specs", "seconds",
                                   "site")}
                for r in self.anomalies()],
        }

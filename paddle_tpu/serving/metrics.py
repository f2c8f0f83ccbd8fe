"""Serving metrics: the observability layer of the serving engine.

Counters and latency distributions a production deployment exports per
engine: time-to-first-token (TTFT), inter-token latency (ITL), decode
throughput, queue depth, slot occupancy, the compile-executable cache
hit/miss counters that back the zero-recompile steady-state guarantee,
and the failure-path counters of the resilience layer (failed/cancelled/
rejected requests, deadline expiries, callback errors, step failures and
retries) plus the engine's ``health()`` snapshot.

``FleetMetrics`` is the same idea one level up: per-fleet supervision
counters (dispatches and affinity hit rate, ejections, rebuilds,
redispatches, failover recovery time) plus a per-replica occupancy table
fed by the router — ``profiler.serving_fleet()`` aggregates every live
fleet.

``snapshot()`` returns a ``/stats``-style plain dict (JSON-serializable).
Each ``ServingMetrics`` registers itself with ``paddle_tpu.profiler`` so
``profiler.serving_stats()`` aggregates every live engine in the process.
"""
from __future__ import annotations

import copy
import time
from collections import deque
from typing import Dict, Optional

__all__ = ["ServingMetrics", "FleetMetrics"]

# Latency distributions keep a bounded sliding window (a long-running
# engine must not grow host memory with traffic); the cumulative totals
# live in the counters.
_LATENCY_WINDOW = 4096


def _dist(xs) -> Dict[str, float]:
    if not xs:
        return {"count": 0, "mean": 0.0, "p50": 0.0, "p99": 0.0, "max": 0.0}
    s = sorted(xs)
    n = len(s)

    def q(p):
        return s[min(n - 1, int(p * (n - 1) + 0.5))]

    return {"count": n, "mean": sum(s) / n, "p50": q(0.5), "p99": q(0.99),
            "max": s[-1]}


class ServingMetrics:
    """Mutable metric sink for one ``serving.Engine``."""

    def __init__(self, name: str = "engine", num_slots: int = 1):
        self.name = name
        self.num_slots = num_slots
        self.t_start = time.perf_counter()
        # counters
        self.requests_enqueued = 0
        self.requests_admitted = 0
        self.requests_completed = 0
        # failure-path counters (the resilience layer's observability:
        # every rejection/cancellation/deadline/retry is visible here)
        self.requests_failed = 0
        self.requests_cancelled = 0
        self.requests_rejected = 0
        self.deadline_expired = 0
        self.callback_errors = 0
        # overload regime (ISSUE 8): preemption evictions and SLO-shed
        # admissions (sheds also count as rejections — a shed IS a
        # rejection, this counter distinguishes the cause)
        self.requests_preempted = 0
        self.requests_shed = 0
        # durability (ISSUE 14): pre-crash terminal outcomes banked from
        # the request journal at recovery (folded into the live counters
        # so completed/failed stay MONOTONE across a process restart —
        # the same banking FleetMetrics does for ejected replicas), plus
        # recovery/hot-swap counters
        self.banked_outcomes: Dict[str, int] = {}
        self.requests_recovered = 0
        self.weight_swaps = 0
        self.model_version = 0
        self.step_failures = 0
        self.step_retries = 0
        self.retries_by_point: Dict[str, int] = {}
        # speculative decoding (ISSUE 15): per-round proposal/acceptance
        # counters — the multiplicative-win observability (accept rate ×
        # (k+1) bounds the target-step savings); spec_cb (set by the
        # engine when speculation is on) contributes the config half
        self.spec_rounds = 0
        self.spec_draft_steps = 0
        self.spec_verify_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_cb = None
        # multi-tenant serving (ISSUE 20): per-tenant SLO accounting —
        # tenant label = adapter name / "grammar:<name>" / "base" — plus
        # the adapter lifecycle counters.  Tenants appear on first
        # traffic; a single-tenant engine exports {"base": ...} only.
        self.tenants: Dict[str, dict] = {}
        self.adapter_loads = 0
        self.adapter_unloads = 0
        # engine-provided liveness snapshot (set by serving.Engine)
        self.health_cb = None
        # paged-KV observability (set by serving.Engine in paged mode):
        # block-pool occupancy, eviction, copy-on-extend, and prefix-hit
        # counters, exported as the snapshot's "paging" section
        self.paging_cb = None
        self.prefix_lookup_errors = 0
        self.prefix_register_errors = 0
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.decode_steps = 0
        self.compile_hits = 0
        self.compile_misses = 0
        self.prefills_by_bucket: Dict[int, int] = {}
        # gauges / distributions
        self.queue_depth = 0
        self.queue_depth_max = 0
        self.ttft_s: deque = deque(maxlen=_LATENCY_WINDOW)
        self.itl_s: deque = deque(maxlen=_LATENCY_WINDOW)
        self.decode_time_s = 0.0
        self.prefill_time_s = 0.0
        self._occupancy_sum = 0.0
        self._occupancy_samples = 0
        self._slots_busy = 0
        from .. import profiler as _profiler

        _profiler._register_serving_metrics(self)

    # -- recording hooks ---------------------------------------------------

    def on_enqueue(self, depth: int) -> None:
        self.requests_enqueued += 1
        self.queue_depth = depth
        self.queue_depth_max = max(self.queue_depth_max, depth)

    def on_admit(self, bucket: int, prompt_len: int, depth: int) -> None:
        self.requests_admitted += 1
        self.prefill_tokens += prompt_len
        self.prefills_by_bucket[bucket] = \
            self.prefills_by_bucket.get(bucket, 0) + 1
        self.queue_depth = depth

    def _tenant(self, tenant: str) -> dict:
        t = self.tenants.get(tenant)
        if t is None:
            t = self.tenants[tenant] = {
                "ttft_s": deque(maxlen=_LATENCY_WINDOW),
                "completed": 0, "failed": 0, "tokens": 0,
            }
        return t

    def on_first_token(self, ttft_s: float,
                       tenant: Optional[str] = None) -> None:
        self.ttft_s.append(ttft_s)
        if tenant is not None:
            self._tenant(tenant)["ttft_s"].append(ttft_s)

    def on_decode_step(self, n_active: int, step_s: float) -> None:
        self.decode_steps += 1
        self.decode_tokens += n_active
        self.decode_time_s += step_s
        # per-token latency for each active stream is the step latency
        self.itl_s.extend([step_s] * n_active)

    def on_spec_round(self, step_s: float, *, draft_steps: int,
                      proposed: int, accepted: int,
                      delivered) -> None:
        """One speculative round: ``draft_steps`` draft dispatches + one
        verify dispatch emitted ``delivered[i]`` tokens per active slot
        (``accepted`` of the ``proposed`` draft tokens survived
        verification; emitted = accepted + one bonus/resample each,
        minus any stop-token truncation).  Folds into the same decode
        token/time counters as plain decode steps so
        ``decode_tokens_per_sec`` and the ITL window stay comparable
        across modes (a burst of n tokens in one round prices each at
        step_s / n)."""
        self.spec_rounds += 1
        self.spec_draft_steps += int(draft_steps)
        self.spec_verify_steps += 1
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)
        self.decode_steps += 1
        self.decode_time_s += step_s
        for n in delivered:
            if n > 0:
                self.decode_tokens += n
                self.itl_s.extend([step_s / n] * n)

    def on_complete(self, tenant: Optional[str] = None,
                    n_tokens: int = 0) -> None:
        self.requests_completed += 1
        if tenant is not None:
            t = self._tenant(tenant)
            t["completed"] += 1
            t["tokens"] += int(n_tokens)

    def on_fail(self, tenant: Optional[str] = None) -> None:
        self.requests_failed += 1
        if tenant is not None:
            self._tenant(tenant)["failed"] += 1

    def on_adapter_load(self, name: str, version: int) -> None:
        """A LoRA adapter was loaded (or hot-swapped) into a pool lane."""
        self.adapter_loads += 1

    def on_adapter_unload(self, name: str, version: int) -> None:
        self.adapter_unloads += 1

    def on_cancel(self) -> None:
        self.requests_cancelled += 1

    def on_reject(self) -> None:
        self.requests_rejected += 1

    def on_deadline(self) -> None:
        self.deadline_expired += 1

    def on_preempt(self, depth: int) -> None:
        """A running request was evicted for a higher-priority admission
        and requeued (NOT a terminal outcome — the request resumes)."""
        self.requests_preempted += 1
        self.queue_depth = depth
        self.queue_depth_max = max(self.queue_depth_max, depth)

    def on_shed(self) -> None:
        """An admission was SLO-shed: its estimated queue wait already
        exceeded its deadline, so it was rejected with ``retry_after_s``
        instead of prefilled doomed."""
        self.requests_shed += 1

    def bank_outcomes(self, outcomes: Dict[str, int]) -> None:
        """Fold a recovered journal's pre-crash terminal counts into the
        live counters (``Engine.recover``): a restarted engine's
        ``requests_completed``/``requests_failed`` continue from where
        the crashed process left off instead of resetting to zero.  The
        raw banked dict stays visible in the snapshot for auditing."""
        total = 0
        for state, n in outcomes.items():
            self.banked_outcomes[state] = \
                self.banked_outcomes.get(state, 0) + int(n)
            total += int(n)
        # the pipeline counters move together so derived gauges
        # (in-flight = enqueued - terminal, completion rate) stay sane:
        # every banked outcome was enqueued — and, rejections aside,
        # admitted — in the crashed process (the fleet-side bank adds
        # to `submitted` for the same reason)
        self.requests_enqueued += total
        self.requests_admitted += total - int(outcomes.get("rejected", 0))
        self.requests_completed += int(outcomes.get("finished", 0))
        self.requests_failed += int(outcomes.get("failed", 0))
        self.requests_cancelled += int(outcomes.get("cancelled", 0))
        self.requests_rejected += int(outcomes.get("rejected", 0))

    def on_recovered(self) -> None:
        """One journaled non-terminal request was rehydrated and
        re-enqueued by crash recovery."""
        self.requests_recovered += 1

    def on_weight_swap(self, version: int) -> None:
        """The engine's weights were hot-swapped in place (drained,
        written through the existing buffers, prefix epoch bumped)."""
        self.weight_swaps += 1
        self.model_version = int(version)

    def on_callback_error(self) -> None:
        self.callback_errors += 1

    def on_prefix_lookup_error(self) -> None:
        """A raising/over-budget prefix-cache lookup degraded to a miss
        (the request still prefills its full prompt)."""
        self.prefix_lookup_errors += 1

    def on_prefix_register_error(self) -> None:
        """Registering a prompt's blocks for future reuse failed — the
        request itself is unaffected, future requests just can't hit
        this prompt.  Counted apart from lookup errors so the two
        degradation modes stay distinguishable on a dashboard."""
        self.prefix_register_errors += 1

    def on_step_failure(self, point: str) -> None:
        self.step_failures += 1

    def on_retry(self, point: str) -> None:
        self.step_retries += 1
        self.retries_by_point[point] = \
            self.retries_by_point.get(point, 0) + 1

    def on_slots(self, busy: int) -> None:
        self._slots_busy = busy
        self._occupancy_sum += busy / max(self.num_slots, 1)
        self._occupancy_samples += 1

    def on_compile(self, miss: bool) -> None:
        if miss:
            self.compile_misses += 1
        else:
            self.compile_hits += 1

    # -- export ------------------------------------------------------------

    def tokens_per_sec(self) -> float:
        return self.decode_tokens / self.decode_time_s \
            if self.decode_time_s > 0 else 0.0

    def _paging_section(self):
        """Engine-fed paged-KV gauges (None with no engine attached)."""
        if self.paging_cb is None:
            return None
        out = self.paging_cb()
        out["prefix_lookup_errors"] = self.prefix_lookup_errors
        out["prefix_register_errors"] = self.prefix_register_errors
        return out

    def _speculation_section(self):
        """Speculative-decoding counters (None when speculation is off —
        the snapshot shape says which mode served the traffic)."""
        if self.spec_cb is None:
            return None
        out = dict(self.spec_cb())
        out.update({
            "rounds": self.spec_rounds,
            "draft_steps": self.spec_draft_steps,
            "verify_steps": self.spec_verify_steps,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate": round(
                self.spec_accepted / self.spec_proposed, 4)
            if self.spec_proposed else 0.0,
            "mean_accepted_per_round": round(
                self.spec_accepted / self.spec_rounds, 4)
            if self.spec_rounds else 0.0,
        })
        return out

    def _tenants_section(self) -> dict:
        """Per-tenant SLO gauges keyed by tenant label, plus the adapter
        lifecycle counters — always present (empty ``by_tenant`` before
        the first tenant-labelled request) so dashboards can bind to the
        shape unconditionally."""
        by_tenant = {}
        for name in sorted(self.tenants):
            t = self.tenants[name]
            by_tenant[name] = {
                "completed": t["completed"],
                "failed": t["failed"],
                "tokens": t["tokens"],
                "ttft_ms": {k: round(v * 1e3, 3) if k != "count" else v
                            for k, v in _dist(t["ttft_s"]).items()},
            }
        return {"adapter_loads": self.adapter_loads,
                "adapter_unloads": self.adapter_unloads,
                "by_tenant": by_tenant}

    def occupancy(self) -> float:
        """Mean busy-slot fraction over all samples so far (0.0 before
        the first step) — shared by ``snapshot()`` and the fleet
        router's per-replica table."""
        return self._occupancy_sum / self._occupancy_samples \
            if self._occupancy_samples else 0.0

    def snapshot(self) -> dict:
        """The ``/stats`` endpoint payload: one JSON-ready dict.  Latency
        distributions cover the last ``_LATENCY_WINDOW`` samples.

        **Copy-on-read guarantee** (ISSUE 9): the returned structure
        shares NO mutable state with the engine — every nested dict and
        list is deep-copied, so a caller mutating (or json-mangling) a
        snapshot can never corrupt live counters, allocator gauges, or
        a health/paging callback's backing store."""
        occ = self.occupancy()
        return copy.deepcopy({
            "name": self.name,
            "uptime_s": round(time.perf_counter() - self.t_start, 3),
            "requests": {
                "enqueued": self.requests_enqueued,
                "admitted": self.requests_admitted,
                "completed": self.requests_completed,
                "running": self._slots_busy,
            },
            "failures": {
                "failed": self.requests_failed,
                "cancelled": self.requests_cancelled,
                "rejected": self.requests_rejected,
                "deadline_expired": self.deadline_expired,
                "callback_errors": self.callback_errors,
                "step_failures": self.step_failures,
                "step_retries": self.step_retries,
                "retries_by_point": dict(sorted(
                    self.retries_by_point.items())),
            },
            "health": self.health_cb() if self.health_cb is not None
            else None,
            "overload": {"preemptions": self.requests_preempted,
                         "shed": self.requests_shed},
            "durability": {
                "recovered": self.requests_recovered,
                "banked": dict(sorted(self.banked_outcomes.items())),
                "weight_swaps": self.weight_swaps,
                "model_version": self.model_version,
            },
            "paging": self._paging_section(),
            "speculation": self._speculation_section(),
            "tenants": self._tenants_section(),
            "queue_depth": self.queue_depth,
            "queue_depth_max": self.queue_depth_max,
            "slot_occupancy": round(occ, 4),
            "slots": {"total": self.num_slots, "busy": self._slots_busy},
            "tokens": {"prefill": self.prefill_tokens,
                       "decode": self.decode_tokens},
            "decode_tokens_per_sec": round(self.tokens_per_sec(), 2),
            "ttft_ms": {k: round(v * 1e3, 3) if k != "count" else v
                        for k, v in _dist(self.ttft_s).items()},
            "inter_token_ms": {k: round(v * 1e3, 3) if k != "count" else v
                               for k, v in _dist(self.itl_s).items()},
            "prefills_by_bucket": dict(sorted(
                self.prefills_by_bucket.items())),
            "compile_cache": {"hits": self.compile_hits,
                              "misses": self.compile_misses},
        })


class FleetMetrics:
    """Mutable metric sink for one ``serving.router.Fleet``.

    Counts fleet-level request outcomes (terminal states are recorded
    here exactly once per request — ``duplicate_terminals`` existing at
    all is the audit that the exactly-once contract held), dispatch
    decisions (total / prefix-affinity / operator-pinned), and the
    supervision loop's actions: ejections, rebuilds (with the measured
    eject→rejoin recovery time — the failover number the serving bench
    reports), and request redispatches.
    """

    def __init__(self, name: str = "fleet", num_replicas: int = 1):
        self.name = name
        self.num_replicas = num_replicas
        self.t_start = time.perf_counter()
        # request outcomes (fleet-level, exactly once per request)
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.cancelled = 0
        self.rejected = 0
        self.duplicate_terminals = 0     # must stay 0: exactly-once audit
        # dispatch decisions
        self.dispatches = 0
        self.affinity_hits = 0
        self.affinity_hit_tokens = 0
        self.pinned_dispatches = 0
        # supervision
        self.redispatches = 0
        self.ejections = 0
        self.rebuilds = 0
        self.rebuild_failures = 0
        self.last_recovery_s: Optional[float] = None
        self.total_recovery_s = 0.0
        # degraded-mode sharded serving: shard-group rebuilds at a
        # smaller viable mp after device loss
        self.degrades = 0
        self.last_degrade_old_mp: Optional[int] = None
        self.last_degrade_mp: Optional[int] = None
        self.last_degrade_s: Optional[float] = None
        self.total_degrade_s = 0.0
        # durability (ISSUE 14): crash recovery + rolling weight rolls
        self.banked_outcomes: Dict[str, int] = {}
        self.requests_recovered = 0
        self.crash_recoveries = 0
        self.last_crash_recovery_s: Optional[float] = None
        self.weight_rolls = 0
        self.last_roll_s: Optional[float] = None
        self.model_version = 0
        # router-provided per-replica table (occupancy, state, queue)
        self.replicas_cb = None
        # router-provided banked flight-recorder dumps, keyed by engine
        # name — merged into profiler.serving_flight_record() so an
        # ejected engine's post-mortem outlives the engine
        self.flight_cb = None
        from .. import profiler as _profiler

        _profiler._register_fleet_metrics(self)

    # -- recording hooks ---------------------------------------------------

    def on_submit(self) -> None:
        self.submitted += 1

    def on_terminal(self, state: str) -> None:
        if state == "finished":
            self.completed += 1
        elif state == "failed":
            self.failed += 1
        elif state == "cancelled":
            self.cancelled += 1
        elif state == "rejected":
            self.rejected += 1

    def on_duplicate_terminal(self) -> None:
        self.duplicate_terminals += 1

    def on_dispatch(self, affinity_tokens: int = 0,
                    pinned: bool = False) -> None:
        self.dispatches += 1
        if pinned:
            self.pinned_dispatches += 1
        elif affinity_tokens > 0:
            self.affinity_hits += 1
            self.affinity_hit_tokens += affinity_tokens

    def on_redispatch(self) -> None:
        self.redispatches += 1

    def on_eject(self) -> None:
        self.ejections += 1

    def on_rebuild(self, recovery_s: float, ok: bool = True) -> None:
        if ok:
            self.rebuilds += 1
            self.last_recovery_s = recovery_s
            self.total_recovery_s += recovery_s
        else:
            self.rebuild_failures += 1

    def on_degrade(self, old_mp: int, new_mp: int,
                   recovery_s: float) -> None:
        """A shard group was rebuilt DEGRADED — at ``new_mp < old_mp``
        on its surviving devices after device loss.  ``recovery_s`` is
        the same eject→rejoin wall time ``on_rebuild`` records (every
        degrade is also counted as a rebuild)."""
        self.degrades += 1
        self.last_degrade_old_mp = int(old_mp)
        self.last_degrade_mp = int(new_mp)
        self.last_degrade_s = recovery_s
        self.total_degrade_s += recovery_s

    def bank_outcomes(self, outcomes: Dict[str, int]) -> None:
        """Fold a recovered journal's pre-crash FINAL terminal counts
        into the fleet counters (``Fleet.recover``) so completed/failed
        stay monotone across a process restart — the same scheme the
        fleet already uses to bank an ejected replica's preemptions."""
        total = 0
        for state, n in outcomes.items():
            self.banked_outcomes[state] = \
                self.banked_outcomes.get(state, 0) + int(n)
            total += int(n)
        self.submitted += total
        self.completed += int(outcomes.get("finished", 0))
        self.failed += int(outcomes.get("failed", 0))
        self.cancelled += int(outcomes.get("cancelled", 0))
        self.rejected += int(outcomes.get("rejected", 0))

    def on_crash_recovery(self, replayed: int, recovery_s: float) -> None:
        self.crash_recoveries += 1
        self.requests_recovered += int(replayed)
        self.last_crash_recovery_s = recovery_s

    def on_weight_roll(self, version: int, roll_s: float) -> None:
        self.weight_rolls += 1
        self.last_roll_s = roll_s
        self.model_version = int(version)

    # -- export ------------------------------------------------------------

    def affinity_hit_rate(self) -> float:
        """Fraction of ROUTED dispatches (operator pins excluded — they
        bypass the policy) that landed on a replica already holding a
        prompt prefix."""
        routed = self.dispatches - self.pinned_dispatches
        return self.affinity_hits / routed if routed else 0.0

    def snapshot(self) -> dict:
        """JSON-ready fleet snapshot, deep-copied like
        :meth:`ServingMetrics.snapshot` (copy-on-read: mutating it
        cannot corrupt the fleet's live counters or replica table)."""
        return copy.deepcopy({
            "name": self.name,
            "uptime_s": round(time.perf_counter() - self.t_start, 3),
            "requests": {
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "cancelled": self.cancelled,
                "rejected": self.rejected,
                "duplicate_terminals": self.duplicate_terminals,
            },
            "dispatch": {
                "total": self.dispatches,
                "affinity_hits": self.affinity_hits,
                "affinity_hit_tokens": self.affinity_hit_tokens,
                "affinity_hit_rate": round(self.affinity_hit_rate(), 4),
                "pinned": self.pinned_dispatches,
                "redispatches": self.redispatches,
            },
            "supervision": {
                "ejections": self.ejections,
                "rebuilds": self.rebuilds,
                "rebuild_failures": self.rebuild_failures,
                "last_recovery_ms": None if self.last_recovery_s is None
                else round(self.last_recovery_s * 1e3, 3),
                "total_recovery_ms": round(self.total_recovery_s * 1e3, 3),
            },
            "degraded": {
                "degrades": self.degrades,
                "last_old_mp": self.last_degrade_old_mp,
                "last_mp": self.last_degrade_mp,
                "last_degrade_ms": None if self.last_degrade_s is None
                else round(self.last_degrade_s * 1e3, 3),
                "total_degrade_ms": round(self.total_degrade_s * 1e3, 3),
            },
            "durability": {
                "crash_recoveries": self.crash_recoveries,
                "recovered": self.requests_recovered,
                "last_crash_recovery_ms":
                    None if self.last_crash_recovery_s is None
                    else round(self.last_crash_recovery_s * 1e3, 3),
                "banked": dict(sorted(self.banked_outcomes.items())),
                "weight_rolls": self.weight_rolls,
                "last_roll_ms": None if self.last_roll_s is None
                else round(self.last_roll_s * 1e3, 3),
                "model_version": self.model_version,
            },
            "replicas": (self.replicas_cb()
                         if self.replicas_cb is not None else None),
        })

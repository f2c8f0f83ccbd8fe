"""Serving fleet supervisor: a replica router over N in-process engines.

PRs 3-5 made one ``Engine`` degrade per-request, never per-engine — but
the engine itself is still a single point of failure: a wedged compiled
step or a corrupted block pool flips a sticky ``unhealthy`` flag and
every queued and in-flight request dies with it.  :class:`Fleet` is the
next containment ring: it owns N engine **replicas** (each with its own
KV pool, prefix cache, and compiled executables) behind one
submit/stream/cancel surface, and treats a replica as a *crashable,
ejectable, restartable unit*:

- **Dispatch** is prefix-affinity first — a request is routed to the
  replica whose :class:`~.prefix_cache.PrefixCache` already covers the
  longest prefix of its prompt (probed side-effect-free via
  ``Engine.prefix_probe``), so cross-request prefix reuse keeps working
  fleet-wide — and least-loaded otherwise, with fleet-level admission
  control aggregating per-replica queue depth.
- **Supervision**: every ``step()`` polls each replica's ``health()``.
  A replica that is ``unhealthy`` (watchdog, allocator-invariant
  violation) or failing consecutively (``eject_after_failures``) is
  **ejected** from rotation; its queued AND in-flight requests are
  exported (``Engine.export_requests``) and **re-dispatched** to
  survivors; the replica is then **rebuilt** (fresh engine over the
  shared model, re-``warmup()``) and rejoins rotation — the fleet heals
  without a process restart, and the eject→rejoin time is exported as
  the measured failover recovery.
- **Redispatch stream contract**: a re-dispatched request replays from
  its prompt — its stream restarts from token 0 with
  ``FleetRequest.redispatched`` / ``.redispatches`` set *before* the
  first replayed token, its ``output_ids`` are reset, and its terminal
  state is reached exactly once (fleet-level, audited by the
  ``duplicate_terminals`` counter).  At most ``max_redispatch`` replays
  are attempted before the request fails with the ejected replica's
  recorded error.  Greedy and seeded-sampling replays are
  deterministic; unseeded temperature sampling redraws (each attempt
  seeds from its per-replica request id).
- **Shape discipline**: replicas are ordinary engines, so no failure
  mode changes a compiled shape on a survivor — ejection, redispatch,
  and rebuild only move host-side bookkeeping, and the chaos tests
  assert survivors' executable-cache miss counters stay flat.

Everything is in-process and CPU-testable; the replica boundary is the
same one the tensor-parallel sharding work (ROADMAP item 1) will land
on, already fault-tolerant.
"""
from __future__ import annotations

import itertools
import time
import weakref
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .engine import (Engine, EngineStopped, PRIORITY_NORMAL, QueueFull,
                     Request, ShedReject, _as_priority)
from .metrics import FleetMetrics
from .sampling import SamplingParams
from .tracing import NULL_TRACER, RequestTracer

__all__ = ["Fleet", "FleetRequest"]

_fleet_counter = itertools.count()

#: Fleet-request states a request can never leave.
FLEET_TERMINAL_STATES = frozenset(
    {"finished", "failed", "cancelled", "rejected"})


@dataclass(eq=False)           # a live handle: identity, not field equality
class FleetRequest:
    """One generation request moving through the fleet.

    The fleet-level handle outlives any single replica attempt: the
    underlying engine :class:`~.engine.Request` is plumbing that may be
    replayed on a different replica after an ejection, while THIS handle
    carries the user-visible stream and reaches a terminal state exactly
    once.  ``output_ids`` mirror the *current* attempt's stream; on
    redispatch they reset to empty and ``redispatches``/``redispatched``
    are set before the first replayed token arrives — the stream
    restarts from token 0, marked.
    """

    prompt_ids: np.ndarray
    request_id: int = -1
    stream_cb: Optional[Callable[[int, "FleetRequest"], None]] = None
    done_cb: Optional[Callable[["FleetRequest"], None]] = None
    kwargs: dict = field(default_factory=dict)    # engine add_request kwargs

    # lifecycle (fleet-managed)
    state: str = "pending"
    error: Optional[str] = None
    #: machine-readable backpressure/shed context — same fields as the
    #: engine-level ``Request.error_ctx`` (``depth``, ``retry_after_s``)
    error_ctx: Optional[dict] = None
    output_ids: List[int] = field(default_factory=list)
    redispatches: int = 0
    redispatched: bool = False
    #: engine-level preemption markers mirrored from the CURRENT attempt
    #: (a preempted stream restarts from token 0, marked — the same
    #: contract as ``redispatched``, one level down)
    preempted: bool = False
    preemptions: int = 0
    #: durable identity in the fleet's request journal: stable across
    #: redispatch AND process crashes (every attempt's admission record
    #: carries it; the exactly-once audit keys on it)
    journal_id: Optional[str] = None
    #: this handle is a crash-recovery replay rehydrated from the
    #: journal by ``Fleet.recover`` (stream restarted from token 0)
    recovered: bool = False
    #: weight version of the replica that admitted the CURRENT attempt
    model_version: int = 0
    #: pre-crash admission wall stamp (tracer's cross-process link)
    _origin_wall: Optional[float] = field(default=None, repr=False)
    #: engine names this request was dispatched to, in order
    replica_history: List[str] = field(default_factory=list)
    t_submit: float = 0.0
    t_finish: Optional[float] = None
    _attempt: Optional[Request] = field(default=None, repr=False)
    _cancel: bool = False
    #: a replica shed this request during the dispatch hunt (the final
    #: rejection may be another replica's plain QueueFull — the fleet
    #: shed counter must still see it)
    _shed_seen: bool = field(default=False, repr=False)
    _fleet: Optional[object] = field(default=None, repr=False)

    @property
    def finished(self) -> bool:
        return self.state == "finished"

    @property
    def done(self) -> bool:
        return self.state in FLEET_TERMINAL_STATES

    def cancel(self) -> bool:
        """Stop this request wherever its current attempt lives.
        Returns False if it is already terminal."""
        if self.done:
            return False
        self._cancel = True
        fleet = self._fleet() if self._fleet is not None else None
        if fleet is not None:
            fleet._on_cancel(self)
        return True


class _Replica:
    """One supervised engine slot in the fleet rotation."""

    __slots__ = ("index", "engine", "state", "ejections", "rebuilds",
                 "rebuild_attempts", "last_error", "_eject_t",
                 "flight_dumps", "degraded")

    def __init__(self, index: int, engine: Engine):
        self.index = index
        self.engine = engine
        self.state = "active"            # active | ejected | dead
        self.ejections = 0
        self.rebuilds = 0
        self.rebuild_attempts = 0        # consecutive failed rebuilds
        self.last_error: Optional[str] = None
        self._eject_t: Optional[float] = None
        #: flight-recorder dumps banked at each ejection — the rebuild
        #: record's post-mortem attachment (the ejected engine itself is
        #: discarded, so the fleet keeps the dump alive)
        self.flight_dumps: List[dict] = []
        #: True once a degraded rebuild shrank this group's mesh below
        #: the fleet's configured ``shards_per_group``
        self.degraded = False

    def load(self) -> int:
        return len(self.engine.queue) + len(self.engine.running)

    def model_parallel(self) -> int:
        shard = getattr(self.engine, "shard", None)
        return shard.mp if shard is not None else 1


class Fleet:
    """N supervised :class:`~.engine.Engine` replicas behind one
    submit/stream/cancel surface.

    Args:
        model_or_config: anything ``Engine.from_config`` accepts (a model
            Layer, a ``GPTConfig``/``LlamaConfig``, or a registry name).
            The model is built ONCE and shared across replicas — weights
            are read-only during serving; each replica owns its own KV
            storage, prefix cache, and compiled executables.
        num_replicas: fleet width.
        max_redispatch: replay budget per request — after this many
            re-dispatches the request fails with the replica's recorded
            error.
        max_queue: fleet-level admission bound on the AGGREGATE queued
            (not-yet-admitted) depth across active replicas; ``None`` =
            unbounded.  A full fleet rejects with :class:`QueueFull`.
        eject_after_failures: eject a replica once its
            ``consecutive_step_failures`` reaches this (in addition to
            any replica whose ``health()`` reports ``unhealthy``).
        supervise_every: run the supervision poll every Nth fleet step
            (1 = every step).
        fault_plan: a shared ``ServingFaultPlan``; each replica's engine
            checks it through a replica-scoped view so
            ``serving.r<k>.<point>`` specs target exactly one replica
            (default: the env-armed plan).
        tracer: a :class:`~.tracing.RequestTracer` shared by the router
            and every replica engine — the fleet-wide request-lifecycle
            span chain (docs/SERVING.md "Tracing & flight recorder").
            Fleet-managed (rejected in ``engine_kwargs``); default: the
            env-armed tracer (``PADDLE_TPU_TRACE=1``) or the no-op
            tracer.
        journal: a :class:`~.journal.RequestJournal` shared by the
            router and every replica — submissions are journaled with
            fleet-scoped ids, the router's exactly-once ``_finish``
            writes each final terminal record, and a fresh process can
            ``recover()`` every non-terminal request after a crash.
            Fleet-managed (rejected in ``engine_kwargs``).
        isolate_weights: give each replica its OWN parameter buffers
            (cloned from the template model) so a rolling
            ``update_weights`` can swap one drained replica while the
            rest keep serving the old weights.  Default None =
            auto: isolate when ``num_replicas > 1`` and the model is
            reconstructible as ``type(model)(model.config)``, else
            share (where ``update_weights`` degrades to a
            stop-the-world swap).
        shards_per_group: tensor-parallel width of each replica.  With
            ``shards_per_group > 1`` every replica is a shard *group* —
            an ``Engine(mesh=...)`` over its own DISJOINT slice of
            ``jax.devices()`` — and the existing per-replica mechanisms
            become the per-group ones the sharded deployment needs:
            prefix-affinity dispatch targets a group, ``update_weights``
            rolls one drained group at a time (per-shard ``_set_data``
            write-through, one prefix-epoch bump per group), and
            recovery replays bitwise onto any mesh of the same shape —
            see docs/SERVING.md "Sharded serving".
        **engine_kwargs: forwarded to every replica's ``Engine(...)``
            (``num_slots``, ``max_seq``, ``block_size``, ...).  ``name``,
            ``fault_plan``, ``tracer``, ``journal``, ``model_version``
            and ``mesh`` are fleet-managed and rejected here.
    """

    def __init__(self, model_or_config, *, num_replicas: int = 2,
                 max_redispatch: int = 2, max_queue: Optional[int] = None,
                 eject_after_failures: int = 2, supervise_every: int = 1,
                 name: Optional[str] = None, fault_plan=None,
                 tracer=None, journal=None,
                 isolate_weights: Optional[bool] = None,
                 shards_per_group: int = 1,
                 **engine_kwargs):
        if num_replicas < 1:
            raise ValueError(f"num_replicas must be >= 1, "
                             f"got {num_replicas}")
        if shards_per_group < 1:
            raise ValueError(f"shards_per_group must be >= 1, "
                             f"got {shards_per_group}")
        if max_redispatch < 0:
            raise ValueError("max_redispatch must be >= 0")
        if eject_after_failures < 1:
            raise ValueError("eject_after_failures must be >= 1")
        if supervise_every < 1:
            raise ValueError("supervise_every must be >= 1")
        for k in ("name", "fault_plan", "tracer", "journal",
                  "model_version", "mesh"):
            if k in engine_kwargs:
                raise ValueError(f"{k!r} is fleet-managed; pass it to "
                                 "Fleet, not through engine kwargs")
        # shard groups (docs/SERVING.md "Sharded serving"): one replica
        # == one shard GROUP — a tensor-parallel engine on its own
        # DISJOINT device slice, so every fleet mechanism built for
        # replicas (prefix-affinity dispatch, per-replica drain in a
        # rolling update_weights, ejection/rebuild, journal recovery)
        # applies to shard groups without a line of new control flow.
        self.shards_per_group = int(shards_per_group)
        self.model = Engine.resolve_model(model_or_config)
        if self.shards_per_group > 1:
            import jax

            from .sharding import serving_mesh, viable_ladder

            # viability at construction (degraded-mode contract): the
            # configured mp must sit ON the model's viability ladder —
            # the same divisibility rules ServingShard enforces, named
            # here so a misconfigured fleet fails with the full ladder
            # (and therefore the degrade steps available to it) instead
            # of a bare divisibility error per engine
            kv, nh = self._head_counts()
            ladder = viable_ladder(kv, nh)
            if self.shards_per_group not in ladder:
                raise ValueError(
                    f"shards_per_group={self.shards_per_group} is not a "
                    f"viable model-parallel degree for this model "
                    f"(kv_heads={kv}, num_attention_heads={nh}): the "
                    f"viable ladder is {ladder} — every mp must divide "
                    f"both head counts so the KV pool shards whole GQA "
                    f"groups")
            devs = jax.devices()
            need = num_replicas * self.shards_per_group
            if len(devs) < need:
                raise ValueError(
                    f"shards_per_group={self.shards_per_group} with "
                    f"num_replicas={num_replicas} needs {need} devices "
                    f"(disjoint per-group meshes), have {len(devs)}")
            #: each group's ORIGINAL device slice — the degraded rebuild
            #: carves its smaller mesh out of whichever of these survive
            self._group_devices: List[Optional[list]] = [
                list(devs[k * self.shards_per_group:
                          (k + 1) * self.shards_per_group])
                for k in range(num_replicas)]
            self._group_meshes: List[Optional[object]] = [
                serving_mesh(self.shards_per_group, devices=slice_)
                for slice_ in self._group_devices]
        else:
            self._group_devices = [None] * num_replicas
            self._group_meshes = [None] * num_replicas
        #: devices recorded lost at ejection (``engine.lost_devices``):
        #: never handed to a rebuilt mesh again
        self._failed_devices: set = set()
        #: current fleet-wide weight version (bumped by update_weights;
        #: replicas join rolls — and rebuilds — at this version)
        self.model_version = 0
        # weight isolation (docs/SERVING.md "Durability & hot swap"):
        # each replica serves its OWN parameter buffers, cloned from
        # the template, so a rolling update can swap one drained
        # replica while the others keep answering on the old weights —
        # exactly the memory layout a multi-process deployment has.
        # isolate_weights=None auto-detects (falls back to the PR 6
        # shared-weights layout when the model cannot be cloned, where
        # update_weights degrades to a documented stop-the-world swap).
        if isolate_weights is None:
            self._isolate_mode = "auto" if num_replicas > 1 else "off"
        else:
            self._isolate_mode = "on" if isolate_weights else "off"
        # provisional under "auto": the first replica clone attempt
        # settles it (falls back to shared on an uncloneable model)
        self.weights_isolated = self._isolate_mode != "off"
        self.name = name or f"fleet-{next(_fleet_counter)}"
        self.num_replicas = int(num_replicas)
        self.max_redispatch = int(max_redispatch)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.eject_after_failures = int(eject_after_failures)
        self.supervise_every = int(supervise_every)
        self._engine_kwargs = dict(engine_kwargs)
        if fault_plan is None:
            from ..distributed.fault_tolerance.injection import \
                ServingFaultPlan

            fault_plan = ServingFaultPlan.from_env()
        self.fault_plan = fault_plan
        # ONE tracer shared by the router and every replica generation:
        # the cross-replica span chain (dispatch → attempt → redispatch)
        # only links up when all parties record into the same tracer
        if tracer is None:
            tracer = RequestTracer.from_env() or NULL_TRACER
        self.tracer = tracer
        # ONE journal shared by the router and every replica: engine
        # admissions/tokens/attempt-ends ride fleet-scoped journal ids,
        # the router's exactly-once _finish writes each final end
        self.journal = journal
        self.replicas: List[_Replica] = [
            _Replica(k, self._make_engine(k))
            for k in range(self.num_replicas)]
        self.metrics = FleetMetrics(self.name,
                                    num_replicas=self.num_replicas)
        self.metrics.replicas_cb = self._replica_rows
        self.metrics.flight_cb = self._flight_dump_table
        self.state = "active"            # active | draining | stopped
        #: live attempt → (fleet request, replica) — the reap table
        self._attempts: Dict[Request, Tuple[FleetRequest, _Replica]] = {}
        #: replica-implicated failures reaped with NO survivor to take
        #: them — held for redispatch after the supervision pass, which
        #: may eject and rebuild the implicated replica this very tick
        self._repatriate: List[Tuple[FleetRequest, str]] = []
        self._req_counter = itertools.count()
        self._rr = 0                     # least-loaded tie-break rotation
        self._tick = 0
        #: preemptions of engines that left rotation (ejected / dead) —
        #: live engines are summed on top in ``stats()``
        self._banked_preemptions = 0
        #: fleet-level shed SUBMITS (counted once per request, however
        #: many replicas shed it while the dispatch hunted for one that
        #: would take it — the per-replica rows keep the raw decisions)
        self._sheds = 0

    # -- replica construction ----------------------------------------------

    def _head_counts(self) -> Tuple[int, int]:
        """(kv_heads, num_attention_heads) of the served model — the
        two divisors the viability ladder is built from (the same
        resolution Engine uses for its ServingShard)."""
        cfg = self.model.config
        kv = getattr(cfg, "n_kv_heads", None) or cfg.num_attention_heads
        return int(kv), int(cfg.num_attention_heads)

    def _replica_model(self):
        """The model a new replica engine serves: a per-replica clone
        of the template (current weights copied in) under weight
        isolation — rebuilt as ``type(model)(model.config)``, true for
        the served GPT/Llama families — else the shared template.
        Rebuilds after an ejection land here too, so a replica rebuilt
        mid-roll joins at the template's CURRENT weights."""
        if not self.weights_isolated:
            return self.model
        try:
            m = type(self.model)(self.model.config)
        except Exception as e:           # noqa: BLE001 — capability probe
            if self._isolate_mode == "auto":
                self.weights_isolated = False
                return self.model
            raise TypeError(
                "isolate_weights=True needs a model reconstructible as "
                "type(model)(model.config) "
                f"({type(e).__name__}: {e}); pass isolate_weights=False "
                "to share weights (rolling update_weights then degrades "
                "to a stop-the-world swap)") from e
        from .engine import _write_state_dict

        _write_state_dict(m, self.model.state_dict(),
                          what="replica model clone")
        m.eval()
        return m

    def _make_engine(self, index: int) -> Engine:
        return Engine(self._replica_model(),
                      name=f"{self.name}.r{index}",
                      fault_plan=self.fault_plan.scoped(index),
                      tracer=self.tracer, journal=self.journal,
                      model_version=self.model_version,
                      mesh=self._group_meshes[index],
                      **self._engine_kwargs)

    def warmup(self) -> dict:
        """Warm every replica (pre-compile all buckets + decode per
        engine) so steady-state serving — and post-failover serving on
        survivors — triggers zero recompiles."""
        return {rep.engine.name: rep.engine.warmup()
                for rep in self.replicas if rep.state == "active"}

    # -- dispatch ----------------------------------------------------------

    def _active(self, exclude: Sequence[_Replica] = ()
                ) -> List[_Replica]:
        return [r for r in self.replicas
                if r.state == "active" and r not in exclude]

    @staticmethod
    def _adapter_of(freq: FleetRequest) -> Optional[str]:
        """The adapter a fleet request selects (None for base) — probes
        ride the tenant's prefix-cache salt, so affinity only credits
        KV the request could actually hit."""
        s = freq.kwargs.get("sampling")
        return getattr(s, "adapter", None) if s is not None else None

    def _choose_replica(self, prompt_ids, exclude: Sequence[_Replica] = (),
                        adapter: Optional[str] = None
                        ) -> Tuple[Optional[_Replica], int]:
        """Dispatch policy: the replica whose prefix cache covers the
        longest prefix of the prompt (ties → least-loaded), else
        least-loaded (ties → round-robin).  Returns
        ``(replica, affinity_tokens)``."""
        cands = self._active(exclude)
        if not cands:
            return None, 0
        probed = [(rep, rep.engine.prefix_probe(prompt_ids,
                                                adapter=adapter))
                  for rep in cands]
        best_hit = max(hit for _, hit in probed)
        if best_hit > 0:
            tied = [rep for rep, hit in probed if hit == best_hit]
            return min(tied, key=self._effective_load), best_hit
        self._rr += 1
        order = cands[self._rr % len(cands):] + \
            cands[:self._rr % len(cands)]
        return min(order, key=self._effective_load), 0

    def _effective_load(self, rep: _Replica) -> float:
        """Dispatch-capacity rebalance: a DEGRADED group (rebuilt at a
        smaller mp after device loss) runs the same slot count on fewer
        chips, so its load is weighted up by ``full_mp / current_mp`` —
        least-loaded dispatch then naturally routes proportionally less
        new traffic to it, without starving it entirely."""
        mp = rep.model_parallel()
        if mp >= self.shards_per_group:
            return float(rep.load())
        return rep.load() * (self.shards_per_group / max(mp, 1))

    def _wrap_stream(self, freq: FleetRequest):
        """Per-attempt stream adapter: mirrors tokens onto the fleet
        handle and forwards to the user's callback with the FLEET
        request (so ``redispatches``/``redispatched`` are visible).  A
        raising user callback propagates into the engine's per-request
        isolation and fails this request (``error_kind="request"`` — a
        callback that raises would raise anywhere, so it is never
        replayed)."""
        def cb(tok: int, ereq: Request) -> None:
            entry = self._attempts.get(ereq)
            if entry is None or entry[0] is not freq:
                return               # stale attempt from an ejected replica
            # mirror the attempt's stream in lockstep, preserving the
            # fleet handle's list identity: the steady state is one
            # append per token; an engine-level preemption reset the
            # attempt's output_ids and restarted its stream from token
            # 0, so any length mismatch resyncs in place
            if len(ereq.output_ids) == len(freq.output_ids) + 1:
                freq.output_ids.append(int(tok))
            else:
                freq.output_ids[:] = ereq.output_ids
            freq.preempted = freq.preempted or ereq.preempted
            freq.preemptions = ereq.preemptions
            if freq.stream_cb is not None:
                freq.stream_cb(int(tok), freq)
        return cb

    def _dispatch(self, freq: FleetRequest,
                  exclude: Sequence[_Replica] = (),
                  pin: Optional[int] = None,
                  redispatch: bool = False) -> None:
        """Place ``freq`` on a replica (raises QueueFull/EngineStopped
        when the fleet genuinely cannot take it; ValueError only from
        enqueue-time validation, with the fleet handle rejected)."""
        excluded = list(exclude)
        while True:
            if pin is not None:
                if not (0 <= pin < self.num_replicas):
                    msg = (f"replica {pin} out of range "
                           f"[0, {self.num_replicas})")
                    self._finish(freq, "rejected", error=msg)
                    err = ValueError(msg)
                    err.request = freq
                    raise err
                rep = self.replicas[pin]
                if rep.state != "active":
                    raise EngineStopped(
                        f"replica {pin} is {rep.state}: cannot pin")
                affinity = 0
            else:
                rep, affinity = self._choose_replica(
                    freq.prompt_ids, excluded,
                    adapter=self._adapter_of(freq))
                if rep is None:
                    raise EngineStopped(
                        f"fleet {self.name!r} has no active replica "
                        "to dispatch to")
            # adoption window: the attempt span the engine creates
            # inside this add_request joins the fleet trace, parented on
            # the previous attempt (the redispatch chain) or the root;
            # the journal adoption mirrors it — every attempt's
            # admission record rides the ONE fleet-scoped journal id
            self.tracer.begin_attempt(freq, rep.engine.name)
            if self.journal is not None:
                if freq.journal_id is None:
                    freq.journal_id = (f"{self.name}:b{self.journal.boot}"
                                       f":f{freq.request_id}")
                self.journal.begin_attempt(
                    freq.journal_id, fleet_owned=True,
                    recovered=freq.recovered,
                    origin_wall=freq._origin_wall)
            try:
                ereq = rep.engine.add_request(
                    freq.prompt_ids, stream_cb=self._wrap_stream(freq),
                    **freq.kwargs)
            except ValueError as e:
                # enqueue-time validation: deterministic, final — with
                # the engine handle's machine-readable context (e.g. an
                # unknown/unloaded adapter's name + version) mirrored
                # onto the fleet handle
                ereq = getattr(e, "request", None)
                if ereq is not None and \
                        getattr(ereq, "error_ctx", None) is not None:
                    freq.error_ctx = dict(ereq.error_ctx)
                self._finish(freq, "rejected",
                             error=getattr(e.request, "error", str(e))
                             if hasattr(e, "request") else str(e))
                e.request = freq
                raise
            except (QueueFull, EngineStopped) as e:
                # this replica can't take it right now — try another
                if isinstance(e, ShedReject):
                    freq._shed_seen = True
                excluded.append(rep)
                if pin is not None or not self._active(excluded):
                    raise
                continue
            finally:
                self.tracer.end_attempt()
                if self.journal is not None:
                    self.journal.end_attempt()
            freq._attempt = ereq
            freq.model_version = rep.engine.model_version
            freq.replica_history.append(rep.engine.name)
            self._attempts[ereq] = (freq, rep)
            self.metrics.on_dispatch(affinity_tokens=affinity,
                                     pinned=pin is not None)
            self.tracer.on_dispatch(freq, rep.engine.name,
                                    redispatch=redispatch,
                                    affinity=affinity)
            return

    # -- public API --------------------------------------------------------

    def submit(self, prompt_ids: Sequence[int], *,
               max_new_tokens: int = 16,
               sampling: Optional[SamplingParams] = None,
               temperature: Optional[float] = None,
               eos_token_id: Optional[int] = None,
               stream_cb: Optional[Callable] = None,
               done_cb: Optional[Callable] = None,
               deadline_s: Optional[float] = None,
               priority=None,
               replica: Optional[int] = None) -> FleetRequest:
        """Enqueue a prompt on the fleet; returns the live
        :class:`FleetRequest` handle.

        Routing is prefix-affinity first, least-loaded otherwise;
        ``replica=<k>`` pins the dispatch (an operator/testing escape
        hatch that bypasses the policy).  A fleet whose aggregate queued
        depth is at ``max_queue`` raises :class:`QueueFull`; malformed
        prompts raise ``ValueError`` with the rejected handle on
        ``.request``.  ``deadline_s`` is a per-ATTEMPT wall-clock budget
        (it restarts on redispatch — a replay is a fresh prefill).
        ``priority`` (``"low"|"normal"|"high"`` or an int) rides in the
        dispatch kwargs, so it is preserved verbatim across redispatch —
        a replayed request keeps its class on the surviving replica."""
        if self.state != "active":
            raise EngineStopped(
                f"fleet {self.name!r} is {self.state}: not admitting "
                "new requests")
        self.metrics.on_submit()
        prompt = np.asarray(list(prompt_ids), dtype=np.int64).reshape(-1)
        if sampling is None and temperature is not None:
            sampling = SamplingParams(temperature=temperature)
        kwargs = {"max_new_tokens": int(max_new_tokens),
                  "eos_token_id": eos_token_id,
                  "deadline_s": deadline_s}
        if priority is not None:
            kwargs["priority"] = priority
        if sampling is not None:
            kwargs["sampling"] = sampling
        freq = FleetRequest(prompt_ids=prompt,
                            request_id=next(self._req_counter),
                            stream_cb=stream_cb, done_cb=done_cb,
                            kwargs=kwargs)
        freq.t_submit = time.perf_counter()
        freq._fleet = weakref.ref(self)
        self.tracer.on_submitted(freq, self.name)
        try:
            # normalized for the backpressure estimate only — kwargs keep
            # the caller's value verbatim for redispatch
            prio = _as_priority(kwargs.get("priority", PRIORITY_NORMAL))
        except ValueError as e:
            # a malformed priority must not leave the handle pending:
            # rejected exactly once, same contract as enqueue validation
            self._finish(freq, "rejected", error=str(e))
            e.request = freq
            raise
        if self.max_queue is not None:
            depth = sum(len(rep.engine.queue) for rep in self._active())
            if depth >= self.max_queue:
                # retry_after_s aggregates the same estimator the
                # engine-level shed uses — priced at THIS request's
                # priority class: the soonest any active replica expects
                # the backlog ahead of it to clear
                waits = [rep.engine.estimate_queue_wait_s(prio)
                         for rep in self._active()]
                retry = round(min(waits), 3) if waits else 0.0
                msg = (f"fleet queue full: {depth} >= "
                       f"max_queue={self.max_queue} across "
                       f"{len(self._active())} active replicas "
                       f"(retry_after_s={retry})")
                freq.error_ctx = {"depth": depth, "retry_after_s": retry}
                self._finish(freq, "rejected", error=msg)
                err = QueueFull(msg, depth, retry_after_s=retry)
                err.request = freq
                raise err
        try:
            self._dispatch(freq, pin=replica)
        except (QueueFull, EngineStopped) as e:
            # no replica could take it: the handle must still terminate
            # (rejected, exactly once) — a submit can never leave a
            # pending request the fleet no longer tracks.  Backpressure
            # and shed context stays machine-readable fleet-side.
            if isinstance(e, QueueFull):
                freq.error_ctx = {"depth": e.depth,
                                  "retry_after_s": e.retry_after_s}
            if isinstance(e, ShedReject) or freq._shed_seen:
                self._sheds += 1         # once per request, not per replica
            if not freq.done:
                self._finish(freq, "rejected", error=str(e))
            e.request = freq
            raise
        return freq

    def step(self) -> bool:
        """One fleet tick: step every active replica that has work, reap
        terminal attempts into fleet outcomes, then run the supervision
        poll (ejection → export/redispatch → rebuild).  Returns True
        while any request is in flight."""
        if self.state == "stopped":
            raise EngineStopped(f"fleet {self.name!r} is stopped")
        for rep in list(self.replicas):
            # "updating" replicas (mid weight-roll drain) keep stepping
            # their in-flight work; they just receive no new dispatches
            if rep.state not in ("active", "updating"):
                continue
            eng = rep.engine
            if (eng.queue or eng.running) and eng.state in (
                    "active", "draining"):
                try:
                    eng.step()
                except EngineStopped:
                    pass                 # unhealthy: supervision ejects it
            self._reap(rep)
        self._tick += 1
        if self._tick % self.supervise_every == 0:
            self._supervise()
            # replays parked for lack of a survivor go out only AFTER a
            # supervision pass — the implicated replica has had its
            # chance to be ejected and rebuilt before it can be chosen
            if self._repatriate:
                batch, self._repatriate = self._repatriate, []
                for freq, err in batch:
                    self._redispatch_or_fail(freq, err)
        return bool(self._attempts or self._repatriate)

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive ``step()`` until every submitted request is terminal
        (or ``max_steps``)."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                break

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 16, **submit_kwargs
                 ) -> List[List[int]]:
        """Synchronous convenience: serve a batch of prompts through the
        fleet; returns generated ids per prompt."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens,
                            **submit_kwargs) for p in prompts]
        self.run()
        return [r.output_ids for r in reqs]

    # -- outcome plumbing --------------------------------------------------

    def _finish(self, freq: FleetRequest, state: str,
                error: Optional[str] = None) -> None:
        """THE single fleet-level terminal transition — guarded so every
        accepted request reaches a terminal state exactly once (a second
        arrival is counted on ``duplicate_terminals``, never applied)."""
        if freq.done:
            self.metrics.on_duplicate_terminal()
            return
        freq.state = state
        if error is not None:
            freq.error = error
        freq.t_finish = time.perf_counter()
        freq._attempt = None
        self.metrics.on_terminal(state)
        self.tracer.on_fleet_terminal(freq, state, error)
        if self.journal is not None and freq.journal_id is not None \
                and self.journal.has_admission(freq.journal_id):
            # THE one final end per journal id (engine-level retires of
            # fleet-owned requests were non-final attempt ends); a
            # rejected submit that never reached an engine admission
            # was delivered synchronously and is not journaled
            self.journal.record_end(
                freq.journal_id, state, final=True, error=freq.error,
                n_tokens=len(freq.output_ids))
        if freq.done_cb is not None:
            try:
                freq.done_cb(freq)
            except Exception:            # noqa: BLE001 — isolation boundary
                pass

    def _reap(self, rep: _Replica) -> None:
        """Map this replica's terminal engine requests onto fleet
        outcomes: finished/user-cancelled/request-fatal failures are
        final; replica-implicated failures re-dispatch within budget."""
        for ereq, (freq, _rep) in list(self._attempts.items()):
            if _rep is not rep or not ereq.done:
                continue
            del self._attempts[ereq]
            if freq.done:                # late echo of a settled request
                continue
            freq._attempt = None
            if getattr(ereq, "error_ctx", None) is not None and \
                    freq.error_ctx is None:
                # machine-readable failure context (adapter unload /
                # hot-swap mid-flight) survives onto the fleet handle
                freq.error_ctx = dict(ereq.error_ctx)
            if ereq.state == "finished":
                self._finish(freq, "finished")
            elif ereq.state == "cancelled":
                if freq._cancel:
                    self._finish(freq, "cancelled")
                elif ereq.error_kind == "replica":
                    # engine lifecycle cancelled it under the fleet
                    # (shutdown/export outside the eject path): replay
                    self._replay(freq, ereq.error, rep)
                else:
                    self._finish(freq, "cancelled", error=ereq.error)
            elif ereq.state == "failed":
                if ereq.error_kind == "replica":
                    self._replay(freq, ereq.error, rep)
                else:
                    self._finish(freq, "failed", error=ereq.error)
            else:                        # "rejected" cannot happen here
                self._finish(freq, ereq.state, error=ereq.error)

    def _replay(self, freq: FleetRequest, error: Optional[str],
                rep: _Replica) -> None:
        """Route a reaped replica-implicated failure: to a SURVIVOR when
        one exists (the implicated replica may still be in rotation,
        pre-ejection — never replay straight back onto it), else hold
        it for the post-supervision pass so a single-replica fleet can
        replay on its own rebuilt engine instead of failing outright."""
        if self._active((rep,)):
            self._redispatch_or_fail(freq, error, exclude=(rep,))
        else:
            self._repatriate.append((freq, error))

    def _redispatch_or_fail(self, freq: FleetRequest,
                            error: Optional[str],
                            exclude: Sequence[_Replica] = ()) -> None:
        """Replay ``freq`` from its prompt on another replica, within
        the at-most-``max_redispatch`` budget; over budget it fails with
        the replica's recorded error.  The stream contract: the marker
        fields flip and ``output_ids`` reset BEFORE the replay's token 0
        can arrive."""
        if freq.done:
            # settled while parked in _repatriate (user cancel between
            # steps): the terminal already happened exactly once
            return
        if freq._cancel:
            self._finish(freq, "cancelled")
            return
        if freq.redispatches >= self.max_redispatch:
            self._finish(
                freq, "failed",
                error=f"redispatch budget exhausted "
                      f"({self.max_redispatch}); last replica error: "
                      f"{error}")
            return
        freq.redispatches += 1
        freq.redispatched = True
        freq.output_ids = []
        self.metrics.on_redispatch()
        try:
            self._dispatch(freq, exclude=exclude, redispatch=True)
        except (QueueFull, EngineStopped) as e:
            self._finish(freq, "failed",
                         error=f"redispatch found no replica: {e}; "
                               f"original replica error: {error}")
        except ValueError:
            # _dispatch already finished it as rejected (cannot really
            # happen on a replay — the prompt validated once already)
            pass

    def _on_cancel(self, freq: FleetRequest) -> None:
        att = freq._attempt
        if att is not None:
            att.cancel()                 # reaped as cancelled next step
        elif not freq.done:
            self._finish(freq, "cancelled")

    # -- supervision -------------------------------------------------------

    def _supervise(self) -> None:
        """The robustness core: eject unhealthy/failing replicas (their
        orphaned requests collected for replay), rebuild every ejected
        replica, then re-dispatch the orphans onto the healed fleet."""
        orphans: List[Tuple[FleetRequest, str]] = []
        orphan_jids: Dict[int, List[str]] = {}
        for rep in self.replicas:
            if rep.state not in ("active", "updating"):
                continue
            h = rep.engine.health()      # also audits paged invariants
            if h["state"] == "unhealthy":
                reason = h.get("reason") or "unhealthy"
            elif h["consecutive_step_failures"] >= \
                    self.eject_after_failures:
                reason = (f"{h['consecutive_step_failures']} consecutive "
                          "compiled-step failures")
            else:
                continue
            mine = self._eject(rep, reason)
            orphans.extend(mine)
            orphan_jids[rep.index] = [
                freq.journal_id for freq, _ in mine
                if freq.journal_id is not None]
        for rep in self.replicas:
            if rep.state == "ejected":
                self._rebuild(rep,
                              orphan_jids=orphan_jids.get(rep.index, []))
        for freq, err in orphans:
            self._redispatch_or_fail(freq, err)

    def _eject(self, rep: _Replica, reason: str
               ) -> List[Tuple[FleetRequest, str]]:
        """Remove a replica from rotation: export its queued + in-flight
        requests for replay, shut the engine down (joins its watchdog
        thread; already-exported work cannot leak), and record why."""
        rep.state = "ejected"
        rep.ejections += 1
        rep._eject_t = time.perf_counter()
        rep.last_error = reason
        # devices the engine recorded lost (serving.shard_fail or real
        # device-loss detection) leave the pool for good: the rebuild
        # carves its mesh from whatever survives
        self._failed_devices.update(
            getattr(rep.engine, "lost_devices", ()))
        # the engine leaves rotation: bank its preemption counter so
        # the fleet aggregate survives the rebuild's fresh engine, and
        # freeze its flight recorder — the last-N-steps post-mortem is
        # attached to the rebuild record and outlives the engine
        self._banked_preemptions += rep.engine.metrics.requests_preempted
        rep.flight_dumps.append(
            rep.engine.flight.dump(f"ejected: {reason}"))
        del rep.flight_dumps[:-8]        # bounded: keep the newest 8
        self.metrics.on_eject()
        self.tracer.on_eject(rep.engine.name, reason)
        err = f"replica {rep.engine.name!r} ejected: {reason}"
        orphans = []
        for ereq in rep.engine.export_requests():
            entry = self._attempts.pop(ereq, None)
            if entry is None:
                continue
            freq = entry[0]
            freq._attempt = None
            if not freq.done:
                orphans.append((freq, err))
        try:
            rep.engine.shutdown(timeout_s=0.0)
        except Exception:                # noqa: BLE001 — already ejected
            pass
        return orphans

    #: consecutive failed rebuilds before a replica is marked ``dead``
    #: and leaves rotation for good — a deterministic rebuild failure
    #: must not spin warmup forever, but one transient hiccup must not
    #: permanently shrink the fleet either (each retry rides a later
    #: supervision pass, one per fleet step).
    MAX_REBUILD_ATTEMPTS = 3

    def _rebuild(self, rep: _Replica,
                 orphan_jids: Sequence[str] = ()) -> None:
        """Heal an ejected replica: fresh engine (fresh pool, fresh
        prefix cache, fresh executables), re-warm, rejoin rotation.  The
        eject→rejoin wall time is the fleet's measured failover
        recovery.

        **Degraded rebuild** (sharded groups): when ejection recorded
        lost devices, the group's surviving slice may no longer fit its
        configured mp — the rebuild then walks DOWN the viability
        ladder to the largest ``mp'`` the survivors support (down to
        ``mp'=1``) and carves a smaller mesh instead of dying.  The
        shape change is journaled as a ``mesh_reshard`` record carrying
        each orphaned request's disposition (``"redispatched"`` — they
        replay through the normal post-supervision pass), the degrade
        is counted/traced, and dispatch capacity rebalances via
        ``_effective_load``.  Only when not even ``mp'=1`` fits (every
        device of the slice lost) does the group go dead."""
        degrade = None                   # (old_mp, new_mp, old_key)
        devs = self._group_devices[rep.index]
        if devs is not None:
            from .sharding import (
                degrade_step, mesh_shape_key, serving_mesh, viable_ladder,
            )

            survivors = [d for d in devs
                         if d not in self._failed_devices]
            old_mesh = self._group_meshes[rep.index]
            old_mp = rep.model_parallel()
            if len(survivors) < old_mp:
                kv, nh = self._head_counts()
                new_mp = degrade_step(kv, nh, len(survivors))
                if new_mp is None:
                    rep.state = "dead"
                    rep.last_error = (
                        f"no viable degraded mesh: {len(survivors)} "
                        f"surviving device(s) in the group, viable "
                        f"ladder {viable_ladder(kv, nh)}")
                    self.metrics.on_rebuild(0.0, ok=False)
                    self.tracer.on_rebuild(rep.engine.name, 0.0,
                                           ok=False)
                    return
                self._group_meshes[rep.index] = serving_mesh(
                    new_mp, devices=survivors)
                degrade = (old_mp, new_mp, mesh_shape_key(old_mesh))
        try:
            eng = self._make_engine(rep.index)
            eng.warmup()
        except Exception as e:           # noqa: BLE001 — isolation boundary
            rep.rebuild_attempts += 1
            rep.state = ("dead" if rep.rebuild_attempts >=
                         self.MAX_REBUILD_ATTEMPTS else "ejected")
            rep.last_error = (f"rebuild failed "
                              f"({rep.rebuild_attempts}/"
                              f"{self.MAX_REBUILD_ATTEMPTS}): "
                              f"{type(e).__name__}: {e}")
            self.metrics.on_rebuild(0.0, ok=False)
            self.tracer.on_rebuild(rep.engine.name, 0.0, ok=False)
            return
        rep.engine = eng
        rep.state = "active"
        rep.rebuilds += 1
        rep.rebuild_attempts = 0
        recovery = time.perf_counter() - (rep._eject_t or
                                          time.perf_counter())
        rep._eject_t = None
        self.metrics.on_rebuild(recovery)
        self.tracer.on_rebuild(eng.name, recovery)
        if degrade is not None:
            old_mp, new_mp, old_key = degrade
            rep.degraded = new_mp < self.shards_per_group
            self.metrics.on_degrade(old_mp, new_mp, recovery)
            self.tracer.on_degrade(eng.name, old_mp, new_mp, recovery)
            if self.journal is not None:
                self.journal.record_mesh_reshard(
                    eng.name, old_key, eng.mesh_shape,
                    {jid: "redispatched" for jid in orphan_jids})

    # -- durability: crash recovery & rolling weight hot-swap --------------

    def recover(self, journal=None) -> dict:
        """Crash-consistent recovery: rehydrate every non-terminal
        journaled request from a previous process's
        :class:`~.journal.RequestJournal` and re-dispatch it across the
        fleet as a replay-from-prompt — ``recovered`` flag set, stream
        restarting at token 0, seeded from the journaled effective seed
        (greedy/seeded outputs bitwise identical to an uninterrupted
        run).  Pre-crash FINAL outcomes are banked into the fleet
        metrics so completed/failed stay monotone across the restart,
        and every replayed request keeps its original journal id — the
        journal-wide exactly-once audit (``duplicate_terminals == 0``)
        spans the crash.

        Call after ``warmup()``, before new traffic.  Returns
        ``{"replayed", "requests", "outcomes", "recovery_ms"}``."""
        journal = journal if journal is not None else self.journal
        if journal is None:
            raise ValueError("recover() needs a RequestJournal (pass "
                             "journal= here or to the Fleet)")
        if self.state != "active":
            raise EngineStopped(
                f"fleet {self.name!r} is {self.state}: cannot recover")
        if self._attempts or self._repatriate or any(
                rep.engine.queue or rep.engine.running
                for rep in self.replicas
                if rep.state in ("active", "updating")):
            # recovery on a LIVE fleet would re-dispatch every request
            # that is still in flight under its own journal id — a
            # guaranteed duplicate terminal (the engine-level recover
            # has the same guard)
            raise RuntimeError(
                "recover() must run before serving traffic: the fleet "
                f"has {self.pending} request(s) in flight whose journal "
                "ids the replay would duplicate")
        if self.journal is None:
            self.journal = journal
            for rep in self.replicas:
                rep.engine.journal = journal
        elif journal is not self.journal:
            # replaying journal B while recording into journal A would
            # leave B's pending set non-converging forever (a later
            # recover from B replays completed work again): one journal
            # per fleet, attached everywhere
            raise ValueError(
                "recover(journal=...) does not match the journal this "
                "fleet records into; recover into the SAME journal the "
                "fleet was constructed with (or construct the fleet "
                "with the journal being recovered)")
        t0 = time.perf_counter()
        outcomes = journal.outcomes()
        self.metrics.bank_outcomes(outcomes)
        replayed = []
        for jid, rec in journal.pending().items():
            replayed.append(self._submit_recovered(jid, rec))
        dt = time.perf_counter() - t0
        self.metrics.on_crash_recovery(len(replayed), dt)
        return {"replayed": len(replayed), "requests": replayed,
                "outcomes": outcomes,
                "recovery_ms": round(dt * 1e3, 3)}

    def _submit_recovered(self, jid: str, rec: dict) -> FleetRequest:
        """One journal replay: a fresh fleet handle carrying the
        ORIGINAL journal id and the journaled replay recipe, dispatched
        outside the fleet ``max_queue`` bound (this work was already
        accepted once — recovery must not shed it on backpressure)."""
        s = self.journal.replay_sampling(rec)
        kwargs = {"max_new_tokens": rec["max_new_tokens"],
                  "eos_token_id": rec["eos_token_id"],
                  "deadline_s": rec["deadline_s"],
                  "priority": rec["priority"],
                  "sampling": SamplingParams(**s)}
        freq = FleetRequest(
            prompt_ids=np.asarray(rec["prompt_ids"],
                                  dtype=np.int64).reshape(-1),
            request_id=next(self._req_counter), kwargs=kwargs)
        freq.journal_id = jid
        freq.recovered = True
        freq._origin_wall = rec.get("wall")
        freq.t_submit = time.perf_counter()
        freq._fleet = weakref.ref(self)
        self.metrics.on_submit()
        self.tracer.on_submitted(freq, self.name)
        problem = self._replay_tenancy_problem(rec, s)
        if problem is not None:
            # a replay whose adapter was unloaded / hot-swapped (or
            # whose grammar is gone) can never be bitwise — fail THIS
            # request with machine-readable context and keep draining
            # the rest of the pending set (never wedge the loop)
            msg, ctx = problem
            freq.error_ctx = ctx
            self._finish(freq, "failed", error=msg)
            return freq
        try:
            self._dispatch(freq)
        except (QueueFull, EngineStopped) as e:
            # the handle still terminates exactly once: a replay no
            # replica can take fails with the reason recorded
            if not freq.done:
                self._finish(freq, "failed",
                             error=f"recovery dispatch found no "
                                   f"replica: {e}")
        except ValueError:
            pass                         # _dispatch already rejected it
        return freq

    def _replay_tenancy_problem(self, rec: dict, s: dict):
        """Can this journaled replay still run bitwise on the current
        fleet?  Returns ``None`` when yes, else ``(message, error_ctx)``
        — the adapter must be loaded at the EXACT journaled version on
        some active replica (an unload or hot-swap in between means the
        replay would run different weights), and the grammar must still
        be registered."""
        a = s.get("adapter")
        if a is not None:
            want = rec.get("adapter_version")
            for rep in self._active():
                pool = getattr(rep.engine, "adapter_pool", None)
                if pool is None:
                    continue
                try:
                    _, v = pool.resolve(a)
                except KeyError:
                    continue
                if want is None or v == want:
                    break
            else:
                return (f"recovery replay rejected: journaled adapter "
                        f"{a!r} (v{want}) is not loaded at that version "
                        f"on any active replica",
                        {"adapter": a, "version": want})
        g = s.get("grammar")
        if g is not None:
            for rep in self._active():
                table = getattr(rep.engine, "grammar_table", None)
                if table is not None and g in table.names:
                    break
            else:
                return (f"recovery replay rejected: journaled grammar "
                        f"{g!r} is not registered on any active "
                        f"replica", {"grammar": g})
        return None

    def load_adapter(self, name: str, weights, *, scale: float = 1.0
                     ) -> int:
        """Load (or hot-swap) a LoRA adapter onto EVERY active replica's
        engine so fleet dispatch stays placement-free — any replica can
        serve any tenant.  Returns the adapter's registry version (all
        replicas agree when loads only go through the fleet).  Replicas
        rebuilt after a failure come back adapter-less: reload through
        this method before routing that tenant's traffic again."""
        if self.state != "active":
            raise EngineStopped(
                f"fleet {self.name!r} is {self.state}: cannot load "
                "adapters")
        version = None
        for rep in self.replicas:
            if rep.state not in ("active", "updating"):
                continue
            version = rep.engine.load_adapter(name, weights, scale=scale)
        if version is None:
            raise EngineStopped(
                f"fleet {self.name!r} has no active replica to load "
                f"adapter {name!r} onto")
        return version

    def unload_adapter(self, name: str) -> int:
        """Unload an adapter from every active replica.  In-flight
        requests of that tenant fail engine-side with machine-readable
        ``error_ctx`` (surfaced onto their fleet handles by ``_reap``);
        the registry remembers the name so version pins from journaled
        admissions keep failing loudly rather than replaying onto
        different weights."""
        if self.state != "active":
            raise EngineStopped(
                f"fleet {self.name!r} is {self.state}: cannot unload "
                "adapters")
        version = None
        for rep in self.replicas:
            if rep.state not in ("active", "updating"):
                continue
            version = rep.engine.unload_adapter(name)
        if version is None:
            raise EngineStopped(
                f"fleet {self.name!r} has no active replica to unload "
                f"adapter {name!r} from")
        return version

    def update_weights(self, state_or_path, *,
                       max_drain_steps: Optional[int] = None) -> dict:
        """Zero-downtime rolling weight hot-swap.

        Under weight isolation (the default for multi-replica fleets),
        replicas are taken out of dispatch rotation ONE AT A TIME
        (state ``updating``), drained of their in-flight work — the
        rest of the fleet keeps answering on the old weights the whole
        time — then swapped in place: the new weights are written
        *through* each replica's existing parameter buffers
        (``Engine.update_weights`` → ``set_state_dict`` write-through),
        so every warmed executable and its lifted state stay valid and
        ZERO new compile keys appear.  Each swap bumps the replica's
        prefix-cache version epoch (a request can never prefix-hit KV
        blocks prefilled under older weights) and its ``model_version``
        tag.  The template model is updated FIRST so a replica ejected
        and rebuilt mid-roll comes back at the new version.

        With shared weights (``isolate_weights=False`` or an
        uncloneable model) there is one parameter set, so the roll
        degrades to a documented stop-the-world swap: every replica is
        drained together, then the single write lands.

        ``max_drain_steps`` bounds each drain (RuntimeError past it —
        the fleet is left serving, partially rolled, with versions
        telling which replica serves what).  Accepts the same weight
        sources as ``Engine.update_weights``.  Returns
        ``{"model_version", "replicas_updated", "roll_ms"}``."""
        from .engine import _resolve_weights, _write_state_dict

        if self.state != "active":
            raise EngineStopped(
                f"fleet {self.name!r} is {self.state}: cannot roll "
                "weights")
        sd = _resolve_weights(state_or_path)
        new_version = self.model_version + 1
        t0 = time.perf_counter()
        updated = 0
        if self.weights_isolated:
            _write_state_dict(self.model, sd)
            self.model_version = new_version
            for rep in list(self.replicas):
                if rep.state != "active":
                    continue             # ejected/dead: rebuilds join
                rep.state = "updating"   # at the new template weights
                try:
                    self._drain_replica(rep, max_drain_steps)
                    if rep.state == "updating":
                        rep.engine.update_weights(sd,
                                                  version=new_version)
                        updated += 1
                finally:
                    if rep.state == "updating":
                        rep.state = "active"
        else:
            # stop-the-world fallback: ONE shared parameter set means
            # no replica can keep serving old weights while another
            # swaps — drain everything, then write once
            marked = [r for r in self.replicas if r.state == "active"]
            for rep in marked:
                rep.state = "updating"
            try:
                for rep in marked:
                    self._drain_replica(rep, max_drain_steps)
            finally:
                for rep in marked:
                    if rep.state == "updating":
                        rep.state = "active"
            _write_state_dict(self.model, sd)
            self.model_version = new_version
            for rep in marked:
                # ONE write through the shared buffers (above); each
                # engine still gets its own epoch/version bookkeeping
                if rep.state == "active" and not (
                        rep.engine.queue or rep.engine.running):
                    rep.engine._mark_weights_swapped(new_version)
                    updated += 1
        dt = time.perf_counter() - t0
        self.metrics.on_weight_roll(new_version, dt)
        self.tracer.on_weight_roll(self.name, new_version, dt, updated)
        return {"model_version": new_version,
                "replicas_updated": updated,
                "roll_ms": round(dt * 1e3, 3)}

    def _drain_replica(self, rep: _Replica,
                       max_drain_steps: Optional[int]) -> None:
        """Drive fleet steps until ``rep`` holds no queued or running
        work (the whole fleet — this replica's in-flight requests
        included — keeps stepping; only new dispatches avoid it).  An
        ejection mid-drain exits early: the rebuilt engine is empty."""
        n = 0
        while rep.state == "updating" and (rep.engine.queue or
                                           rep.engine.running):
            self.step()
            n += 1
            if max_drain_steps is not None and n >= max_drain_steps:
                raise RuntimeError(
                    f"replica {rep.engine.name!r} did not drain within "
                    f"{max_drain_steps} fleet steps (still "
                    f"{len(rep.engine.running)} running, "
                    f"{len(rep.engine.queue)} queued)")

    # -- lifecycle ---------------------------------------------------------

    def drain(self, max_steps: Optional[int] = None) -> dict:
        """Stop admitting, finish every in-flight request (supervision —
        ejection and rebuild included — keeps running while draining),
        stop all replicas, and return the final stats snapshot."""
        if self.state == "active":
            self.state = "draining"
        n = 0
        while (self._attempts or self._repatriate) and \
                self.state == "draining":
            self.step()
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        for rep in self.replicas:
            if rep.state == "active":
                rep.engine.drain()
        # the engine drains above may have finished work the step loop
        # never saw (max_steps cut it short): reap it into fleet
        # terminals so every done_cb fires and pending reaches 0
        for rep in self.replicas:
            if rep.state == "active":
                self._reap(rep)
        if not (self._attempts or self._repatriate):
            self.state = "stopped"
        return self.stats()

    def shutdown(self, timeout_s: Optional[float] = None) -> dict:
        """Drain within a wall-clock budget, then cancel whatever is
        still unfinished and stop every replica."""
        if self.state == "active":
            self.state = "draining"
        deadline = None if timeout_s is None \
            else time.perf_counter() + float(timeout_s)
        while (self._attempts or self._repatriate) and \
                self.state == "draining":
            if deadline is not None and time.perf_counter() >= deadline:
                break
            self.step()
        for ereq, (freq, _rep) in list(self._attempts.items()):
            del self._attempts[ereq]
            self._finish(freq, "cancelled", error="fleet shutdown")
        for freq, _err in self._repatriate:
            if not freq.done:
                self._finish(freq, "cancelled", error="fleet shutdown")
        self._repatriate.clear()
        for rep in self.replicas:
            if rep.state == "active":
                try:
                    rep.engine.shutdown(timeout_s=0.0)
                except Exception:        # noqa: BLE001 — best effort
                    pass
        self.state = "stopped"
        return self.stats()

    # -- observability -----------------------------------------------------

    @property
    def pending(self) -> int:
        """Accepted requests not yet terminal."""
        return len(self._attempts) + len(self._repatriate)

    def _replica_rows(self) -> List[dict]:
        rows = []
        for rep in self.replicas:
            eng = rep.engine
            m = eng.metrics
            rows.append({
                "index": rep.index,
                "name": eng.name,
                "state": rep.state,
                "engine_state": eng.state,
                "ejections": rep.ejections,
                "rebuilds": rep.rebuilds,
                "last_error": rep.last_error,
                "queue_depth": len(eng.queue),
                "slots_busy": len(eng.running),
                "slots_total": eng.num_slots,
                "occupancy": round(m.occupancy(), 4),
                "compile_misses": m.compile_misses,
                "mesh_shape": eng.mesh_shape,
                "model_parallel": rep.model_parallel(),
                "degraded": rep.degraded,
                "preemptions": m.requests_preempted,
                "shed": m.requests_shed,
                # the rebuild record's post-mortem attachment: a summary
                # of the flight dump frozen at the last ejection (the
                # full dump rides profiler.serving_flight_record())
                "last_flight_record": (
                    {"reason": rep.flight_dumps[-1]["reason"],
                     "steps_seen": rep.flight_dumps[-1]["steps_seen"],
                     "events": len(rep.flight_dumps[-1]["events"])}
                    if rep.flight_dumps else None),
            })
        return rows

    def _flight_dump_table(self) -> Dict[str, List[dict]]:
        """Banked ejection dumps per engine name — merged into
        ``profiler.serving_flight_record()`` so a dump survives its
        (discarded) engine."""
        out: Dict[str, List[dict]] = {}
        for rep in self.replicas:
            if rep.flight_dumps:
                out.setdefault(rep.engine.name, []).extend(
                    rep.flight_dumps)
        return out

    def _overload_section(self) -> dict:
        """Fleet-wide overload totals: preemptions are per-engine events
        (banked from ejected engines plus every in-rotation engine's
        live counter); ``shed`` counts fleet-level shed *submits* —
        once per request, even when several replicas shed it before the
        dispatch gave up."""
        pre = self._banked_preemptions
        for rep in self.replicas:
            if rep.state != "active":
                continue                 # ejected engines are banked
            pre += rep.engine.metrics.requests_preempted
        return {"preemptions": pre, "shed": self._sheds}

    def health(self) -> dict:
        """Fleet liveness probe: fleet state, per-replica health, and
        in-flight depth — the load-balancer view one level above
        ``Engine.health()``."""
        return {
            "state": self.state,
            "pending": self.pending,
            "active_replicas": len(self._active()),
            "replicas": {rep.engine.name: {
                "replica_state": rep.state,
                **rep.engine.health(),
            } for rep in self.replicas},
        }

    def stats(self) -> dict:
        """``/stats``-style snapshot (also exported through
        ``paddle_tpu.profiler.serving_fleet()``): the fleet metrics plus
        each replica's full engine snapshot."""
        out = self.metrics.snapshot()
        out["state"] = self.state
        out["pending"] = self.pending
        out["durability"]["weights_isolated"] = self.weights_isolated
        if self.journal is not None:
            out["durability"]["journal"] = self.journal.stats()
        out["overload"] = self._overload_section()
        # degraded-mode view (docs/SERVING.md "Degraded sharded
        # serving"): the FleetMetrics "degraded" counters plus the live
        # per-group mp and the devices the fleet has written off
        out.setdefault("degraded", {})
        out["degraded"]["failed_devices"] = len(self._failed_devices)
        out["degraded"]["groups"] = {
            rep.engine.name: {
                "model_parallel": rep.model_parallel(),
                "configured": self.shards_per_group,
                "degraded": rep.degraded,
                "state": rep.state,
            } for rep in self.replicas}
        if self.tracer.enabled:
            out["tracing"] = self.tracer.snapshot()
        out["engines"] = {rep.engine.name: rep.engine.stats()
                          for rep in self.replicas}
        return out

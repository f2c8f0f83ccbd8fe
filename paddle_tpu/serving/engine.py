"""Continuous-batching serving engine: slot scheduler over a KV cache.

The TPU-idiomatic serving loop (XLA recompiles on every new shape, so the
engine is built so that NO shape ever depends on request content):

- **prefill**: each admitted request's prompt is padded to a power-of-two
  bucket and run through the model's causal forward once, writing K/V into
  the request's slot.  One executable per bucket; the slot index and true
  prompt length are *arguments*, so all slots share the executables.
- **decode**: every step runs ONE fixed-shape program over all slots
  (``[slots, 1]`` tokens + ``[slots]`` active mask), each active slot
  extending its sequence by one token via ``ops.cached_attention``.
  Admitting or retiring a request only changes argument *values* —
  steady-state serving triggers zero recompiles (asserted by tests via the
  executable cache's own hit/miss counters).

Requests are admitted into free slots as they arrive and retired the step
they finish (eos / token budget / cache capacity), in the spirit of
fine-grained compute/host-scheduling overlap (T3, arXiv:2401.16677).

Decode hot path (docs/SERVING.md "Decode hot path"): a decode step is ONE
device dispatch with ZERO blocking host transfers.  Sampling runs inside
the compiled step (``serving.sampling.DeviceSampler``: per-slot
temperature/top-k/top-p lanes and ``jax.random`` key state lifted like KV
cache state), the sampled token ids feed the next step's inputs
device-side through the sampler's token lane, and the attention itself
consumes the paged pool's block table inside a Pallas flash-decoding
kernel (``kernel="pallas"``, the default; ``"reference"`` keeps the jnp
gather oracle).  The host touches only the tiny ``[slots] int32`` token
array — for stream delivery and stop checks, pulled AFTER the sanitizer's
blocking-transfer window closes — so the sanitizer's measured
``serving_decode_host_transfers`` is 0.0 (down from the 1.0 logits-pull
baseline PR 7 priced).

Resilience (docs/SERVING.md "Failure semantics"): the scheduler degrades
per-request, never per-engine.  Requests own terminal states
``finished | failed | cancelled | rejected`` plus an ``error`` record;
every exit path funnels through ``_retire`` so a slot (and its cache
length) can never leak.  A raising ``stream_cb`` or sampling failure fails
only its request; a failed compiled step retries once with backoff before
failing only the implicated requests.  Admission is bounded
(``max_queue`` + reject/block policy), deadlines are wall-clock and
enforced in ``step()``, and ``drain()``/``shutdown()``/``health()`` give
the engine an explicit lifecycle.  None of this changes any compiled
shape: deadlines, cancellation, and retirement only alter argument
values, so the zero-recompile steady state survives every failure path.

Overload (docs/SERVING.md "Overload, priorities & preemption"): sustained
pressure is a first-class regime, not a failure mode.  Requests carry a
**priority class** (``PRIORITY_LOW|NORMAL|HIGH`` or any int); the queue
is served highest-effective-priority first with **deferral aging**
(``priority_aging_s`` — a waiting request's effective priority rises over
time, so low-priority work is never starved).  When no slot — or no
KV block — can serve a higher-priority admission, the
scheduler **preempts** the lowest-priority running victim: its prompt
blocks are registered in the prefix cache *before* its slot releases
(resume becomes a cheap prefix hit), and it requeues replay-from-prompt
with ``preempted``/``preemptions`` set and its stream restarting from
token 0 — the fleet redispatch stream contract, one level down.  At most
``max_preemptions`` evictions per request; past the budget a request is
immune.  **SLO-aware shedding** rejects at admission (``ShedReject``,
with ``retry_after_s``) any deadline-carrying request whose estimated
queue wait already exceeds its deadline, instead of prefilling doomed
work.  All of it is host-side bookkeeping: preemption and resume reuse
the existing prefill buckets and add ZERO executable-cache keys
(provable against tools/shape_manifest.json).
"""
from __future__ import annotations

import itertools
import os
import time
import weakref
from collections import OrderedDict, deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, to_tensor
from ..obs import spans as _spans
from .kv_cache import cache_spec_of
from .metrics import ServingMetrics
from .paging import PagedCacheContext, PagedKVCache
from .prefix_cache import ChainKeys, PrefixCache
from .group_cache import GroupedKVCache, GroupedPrefixCache
from .window_cache import WindowedKVCache, WindowedPrefixCache
from .sampling import DeviceSampler, SamplingParams, sampler_path
from .sanitize import SyncSanitizer
from .staging import SlotStager
from .tracing import NULL_TRACER, FlightRecorder, RequestTracer

__all__ = ["Engine", "Request", "SamplingParams", "QueueFull",
           "ShedReject", "EngineStopped",
           "PRIORITY_LOW", "PRIORITY_NORMAL", "PRIORITY_HIGH"]

_engine_counter = itertools.count()

#: Request states a request can never leave.
TERMINAL_STATES = frozenset({"finished", "failed", "cancelled", "rejected"})

#: Priority classes (any int works; higher serves first).
PRIORITY_LOW, PRIORITY_NORMAL, PRIORITY_HIGH = 0, 1, 2

_PRIORITY_NAMES = {"low": PRIORITY_LOW, "normal": PRIORITY_NORMAL,
                   "high": PRIORITY_HIGH}


def _as_priority(priority) -> int:
    """Normalize a priority class: ``"low"|"normal"|"high"`` or any int
    (higher = served first)."""
    if isinstance(priority, str):
        try:
            return _PRIORITY_NAMES[priority.lower()]
        except KeyError:
            raise ValueError(
                f"unknown priority {priority!r}; want one of "
                f"{sorted(_PRIORITY_NAMES)} or an int") from None
    return int(priority)


def _resolve_weights(state_or_path):
    """Normalize ``update_weights`` input to a flat state dict: a dict
    passes through, a ``.npz`` path loads its arrays, a directory loads
    a ``distributed.checkpoint.save_state_dict`` checkpoint (the
    fault-tolerant training stack's output format)."""
    if isinstance(state_or_path, dict):
        return state_or_path
    if isinstance(state_or_path, (str, os.PathLike)):
        p = os.fspath(state_or_path)
        if os.path.isdir(p):
            from ..distributed.checkpoint import load_state_dict

            return load_state_dict(p)
        if p.endswith(".npz"):
            with np.load(p) as z:
                return {k: z[k] for k in z.files}
        raise ValueError(
            f"update_weights: {p!r} is neither a checkpoint directory "
            "nor an .npz file")
    raise TypeError(
        "update_weights wants a state dict, a checkpoint directory, or "
        f"an .npz path, got {type(state_or_path).__name__}")


def _write_state_dict(model, sd, what: str = "update_weights") -> None:
    """Write ``sd`` through ``model``'s existing buffers and insist on
    full coverage — the one shared coverage check for every weight-swap
    write site (a partial write would serve a frankenmodel)."""
    missing, unexpected = model.set_state_dict(sd)
    if missing or unexpected:
        raise ValueError(
            f"{what}: state dict does not cover the model "
            f"(missing={missing[:5]}, unexpected={unexpected[:5]})")


class QueueFull(RuntimeError):
    """Admission rejected by backpressure: the request queue is at
    ``max_queue`` (and, under the ``block`` policy, stayed full past the
    block timeout).  Carries the observed ``depth`` and the engine's
    estimated ``retry_after_s`` (machine-readable; also mirrored on the
    rejected handle's ``Request.error_ctx``)."""

    def __init__(self, msg: str, depth: int,
                 retry_after_s: Optional[float] = None):
        super().__init__(msg)
        self.depth = depth
        self.retry_after_s = retry_after_s


class ShedReject(QueueFull):
    """SLO-aware admission shed: the request carries a wall-clock
    deadline its estimated queue wait already exceeds — prefilling it
    would burn a compiled prefill on work that is doomed to miss its
    SLO.  Subclasses :class:`QueueFull` so backpressure-aware callers
    (the fleet router included) handle both identically; ``retry_after_s``
    says when the backlog is expected to have cleared."""


class EngineStopped(RuntimeError):
    """``add_request`` after ``drain()``/``shutdown()`` (or on an
    unhealthy engine): the engine no longer admits work."""


@dataclass(eq=False)           # a live handle: identity, not field equality
class Request:
    """One generation request moving through the engine.

    State machine: ``queued → running → finished | failed | cancelled``;
    malformed or backpressured requests go straight to ``rejected`` at
    enqueue time and are never admitted.  ``error`` records why a request
    ended ``failed``/``rejected``.
    """

    prompt_ids: np.ndarray
    max_new_tokens: int = 16
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_token_id: Optional[int] = None
    stream_cb: Optional[Callable[[int, "Request"], None]] = None
    request_id: int = -1
    deadline_s: Optional[float] = None   # wall-clock budget from enqueue
    #: priority class (``PRIORITY_LOW|NORMAL|HIGH`` or any int; higher
    #: serves first).  Queue ordering uses the *effective* priority —
    #: this plus the deferral-aging boost — while preemption rights
    #: compare base classes only.
    priority: int = PRIORITY_NORMAL

    # lifecycle (engine-managed)
    state: str = "queued"
    _defers: int = 0                     # paged admissions deferred so far
    _hit_given_up: int = 0               # tokens its last prefix hit was
    #                                      shortened by (a cache by layer)
    #: set when the scheduler evicted this request mid-flight to serve a
    #: higher-priority admission; the stream restarted from token 0 on
    #: resume (``preemptions`` counts the evictions)
    preempted: bool = False
    preemptions: int = 0
    #: durable identity in the request journal (``Engine(journal=...)``);
    #: stable across preemption, redispatch, AND process crashes — the
    #: exactly-once terminal audit keys on it
    journal_id: Optional[str] = None
    #: set when this admission is a crash-recovery replay rehydrated
    #: from the journal: the stream restarted from token 0 (the
    #: redispatch contract, one process-death further out)
    recovered: bool = False
    #: weight version the serving engine held when this request was
    #: admitted (bumped by rolling hot-swaps; 0 = initial weights)
    model_version: int = 0
    #: tenant label for SLO accounting: the adapter name if the request
    #: selects one, else ``"grammar:<name>"`` for grammar-only requests,
    #: else ``"base"`` — threaded into metrics and the tracer
    tenant: str = "base"
    #: adapter version pinned at enqueue (None when no adapter): a
    #: hot-swap or unload of that adapter fails this request rather than
    #: serving a torn hybrid, and recovery refuses to replay onto any
    #: other version
    adapter_version: Optional[int] = None
    error: Optional[str] = None
    #: machine-readable context for backpressure/shed rejections
    #: (``{"depth": int, "retry_after_s": float}``)
    error_ctx: Optional[dict] = None
    #: who a failure implicates: ``"request"`` (this request's own prompt,
    #: callback, sampling, or deadline — retrying elsewhere would fail the
    #: same way) vs ``"replica"`` (the engine's compiled step / lifecycle
    #: failed under it — a fleet supervisor may replay it on a survivor)
    error_kind: str = "request"
    slot: Optional[int] = None
    output_ids: List[int] = field(default_factory=list)
    prefill_bucket: int = 0
    t_enqueue: float = 0.0
    t_first_token: Optional[float] = None
    t_finish: Optional[float] = None
    _seq_len: int = 0                # prompt + emitted tokens in the cache
    _cancel: bool = False
    #: the prompt's prefix-cache chain keys, hashed by its first lookup and
    #: read by the capped re-lookup, the registration and every retry of a
    #: deferred or preempted admission (``prefix_cache.ChainKeys``)
    _keys: ChainKeys = field(default_factory=ChainKeys, repr=False)
    _engine: Optional[object] = field(default=None, repr=False)

    @property
    def finished(self) -> bool:
        return self.state == "finished"

    @property
    def done(self) -> bool:
        return self.state in TERMINAL_STATES

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_enqueue

    def cancel(self) -> bool:
        """Ask the engine to stop this request.  Honored immediately while
        queued; a running request is retired ``cancelled`` at the next
        step boundary (before its next decode).  Returns False if the
        request is already terminal."""
        if self.done:
            return False
        self._cancel = True
        eng = self._engine() if self._engine is not None else None
        if eng is not None:
            eng._on_cancel(self)
        elif self.state == "queued":
            self.state = "cancelled"
        return True


class Engine:
    """Slot-based continuous-batching engine over a causal-LM model.

    Args:
        model: ``GPTForCausalLM`` / ``LlamaForCausalLM`` (any Layer whose
            forward accepts ``cache_ctx`` works).  Switched to eval mode.
        num_slots: fixed decode batch width.
        max_seq: per-slot cache capacity (prompt + generated); defaults to
            the model's ``max_position_embeddings``.
        min_bucket: smallest prefill bucket (default: one default
            ``block_size``); buckets are powers of two up to ``max_seq``.
        cache_dtype: KV cache dtype (default: the model's param dtype).
        max_queue: bound on queued (not-yet-admitted) requests; ``None``
            (default) is unbounded.
        queue_policy: what a full queue does to ``add_request``:
            ``"reject"`` raises :class:`QueueFull` immediately; ``"block"``
            drives ``step()`` until space frees or ``block_timeout_s``
            elapses (then raises :class:`QueueFull`).
        block_timeout_s: default wait budget for the ``block`` policy.
        default_deadline_s: wall-clock deadline applied to requests that
            set none themselves (``None`` = no deadline).
        max_step_retries: how many times a failed compiled prefill/decode
            call is retried (with exponential backoff) before the
            implicated requests are failed.  Safe because compiled-state
            writeback happens only after a step returns successfully.
        retry_backoff_s: base backoff before the first retry.
        step_timeout_s: arm a ``StepWatchdog`` around every compiled step;
            a call exceeding the deadline dumps all thread stacks and
            flips the engine to the ``unhealthy`` state (visible via
            ``health()``) instead of wedging silently.
        fault_plan: a ``ServingFaultPlan`` for chaos testing; defaults to
            the env-armed plan (``PADDLE_TPU_FT_SERVING_FAULTS``).
        kv_layout: no choice: the cache is always the paged pool
            (block-pool KV storage addressed through per-slot block
            tables, with refcounted cross-request prefix reuse — see
            docs/SERVING.md "Paged KV cache").  The keyword survives
            for callers that still pass ``"paged"``; any other value
            raises.
        kernel: attention path over the pool — ``"pallas"`` (default:
            the flash-decoding/fused-prefill kernels that consume the
            block table in-kernel; interpret mode off-TPU so CPU runs
            the same code path) or ``"reference"``, the jnp gather +
            masked-softmax oracle the kernels are tested against.
            Selection never changes a compiled shape — see
            docs/SERVING.md "Decode hot path".
        block_size: tokens per KV block; must divide ``min_bucket``
            (and therefore every prefill bucket) and ``max_seq``.
        num_kv_blocks: pool size; default
            ``num_slots * max_seq / block_size + 1`` (every slot at
            ``max_seq`` plus the reserved scratch block).
        num_summary_blocks: blocks of the summary group, one a window,
            of a model whose cache is ``CacheSpec.windowed`` (then
            ``num_kv_blocks`` sizes the exact group, default two windows
            a slot and one cold prompt); default every slot at
            ``max_seq``.  Refused for any other model.
        num_window_blocks: blocks of the group that keeps a window, of a
            model whose cache is stated by layer (``CacheSpec.by_layer``:
            then ``num_kv_blocks`` sizes the group that keeps every token);
            default a window and a block a slot and two tails' room.
            Refused for any other model.
        num_state_snapshots: rows of the snapshot pool of a group that
            keeps state (``CacheGroup.state``), row 0 the zeros; default
            what every slot's longest tail could write.  Refused for a
            model that states no such group.
        enable_prefix_cache: hash whole prompt blocks host-side and
            serve repeated prefixes from refcounted shared blocks,
            shrinking the prefill to the uncached tail bucket.
        prefix_lookup_timeout_s: classifier for a degraded prefix cache:
            a lookup that took longer than this (the lookup is
            synchronous, so the time is already spent) is treated as a
            failed subsystem — its result is discarded, the admission
            proceeds as a plain miss, and ``paging.prefix_lookup_errors``
            is counted — keeping degraded-mode behavior deterministic
            (the same contract as a *raising* lookup).
        max_preemptions: how many times one request may be evicted
            mid-flight to make room for a higher-priority admission;
            past the budget it is immune to further preemption.  0
            disables preemption entirely.
        priority_aging_s: deferral-aging interval — a queued request's
            effective priority rises by one class per this many seconds
            of wait, so sustained high-priority traffic can never starve
            lower classes (``None`` disables aging).  Aging affects
            queue *ordering* only; preemption rights always compare base
            priority classes, so equal-priority workloads never churn.
        tracer: a :class:`~.tracing.RequestTracer` recording this
            engine's per-request lifecycle span chain (share ONE tracer
            across a fleet's replicas for the cross-replica story).
            Default: the env-armed tracer (``PADDLE_TPU_TRACE=1``) or
            the no-op :data:`~.tracing.NULL_TRACER` — tracing off costs
            nothing on the decode hot path.
        flight_recorder_steps: ring capacity of the always-on
            :class:`~.tracing.FlightRecorder` (the last N step
            summaries, dumped automatically when ``health()`` flips
            unhealthy or the fleet ejects this replica).
        journal: a :class:`~.journal.RequestJournal` — every accepted
            request is journaled durably (admission with the full
            replay recipe, batched per-step token records, terminal
            record) so a fresh process can ``recover()`` it after a
            crash.  Default None: no journaling, no overhead.  Share
            ONE journal across a fleet (fleet-managed there).
        model_version: initial weight version tag (bumped in place by
            ``update_weights``; each request records the version that
            served it).
        speculation: a :class:`~.spec_decode.SpecConfig` opting this
            engine into speculative decoding (draft-model propose, one
            bucketed ``[slots, k+1]`` verify step, device-side
            rejection-sampling accept).  Off (None) by default — the
            decode loop is unchanged.  When on, ``step()`` becomes
            round-based: k draft steps + one verify step per scheduler
            tick, emitting 1..k+1 tokens per slot per round.  Greedy
            output stays bitwise identical to non-speculative decoding;
            seeded sampling stays distribution-preserving — see
            docs/SERVING.md "Speculative decoding".
        adapters: an :class:`~.adapters.AdapterConfig` (or its kwargs as
            a dict) opting this engine into multi-LoRA serving: stacked
            per-target adapter lanes + a per-slot adapter-id lane, all
            lifted compiled-step state (ZERO new cache keys), with
            requests selecting a loaded adapter via
            ``SamplingParams.adapter``.  None (default) attaches no
            hooks — the model trace is byte-identical to pre-tenancy.
            See docs/SERVING.md "Multi-tenant serving".
        grammars: a dict mapping grammar name →
            :class:`~.grammar.JsonArrayGrammar`-style spec (or a ready
            :class:`~.grammar.GrammarTable`) opting this engine into
            constrained decoding: requests select a grammar via
            ``SamplingParams.grammar`` and the sampler masks illegal
            tokens in-graph, composing with greedy/temperature/top-k/
            top-p AND speculative verify.  None (default) = no grammar
            lanes.
    """

    def __init__(self, model, *, num_slots: int = 4,
                 max_seq: Optional[int] = None, min_bucket: int = 16,
                 cache_dtype=None, name: Optional[str] = None,
                 max_queue: Optional[int] = None,
                 queue_policy: str = "reject",
                 block_timeout_s: float = 30.0,
                 default_deadline_s: Optional[float] = None,
                 max_step_retries: int = 1,
                 retry_backoff_s: float = 0.05,
                 step_timeout_s: Optional[float] = None,
                 fault_plan=None,
                 kv_layout: str = "paged",
                 kernel: str = "pallas",
                 block_size: int = 16,
                 num_kv_blocks: Optional[int] = None,
                 num_summary_blocks: Optional[int] = None,
                 num_window_blocks: Optional[int] = None,
                 num_state_snapshots: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 prefix_lookup_timeout_s: float = 0.25,
                 max_preemptions: int = 2,
                 priority_aging_s: Optional[float] = 5.0,
                 tracer=None,
                 flight_recorder_steps: int = 256,
                 journal=None,
                 model_version: int = 0,
                 speculation=None,
                 adapters=None,
                 grammars=None,
                 mesh=None):
        cfg = getattr(model, "config", None)
        if cfg is None:
            raise TypeError("Engine needs a model carrying a .config "
                            "(GPTForCausalLM / LlamaForCausalLM)")
        self.model = model
        self.model.eval()
        self.config = cfg
        max_pos = getattr(cfg, "max_position_embeddings", None)
        if max_seq is None and max_pos is None:
            raise ValueError("max_seq is required: the model config has no "
                             "max_position_embeddings to default to")
        self.max_seq = int(max_seq or max_pos)
        if max_pos is not None and self.max_seq > max_pos:
            raise ValueError(
                f"max_seq {self.max_seq} exceeds the model's "
                f"max_position_embeddings {max_pos}")
        self.num_slots = int(num_slots)
        self.min_bucket = int(min_bucket)
        if self.min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, got {min_bucket}")
        if queue_policy not in ("reject", "block"):
            raise ValueError(f"queue_policy must be 'reject' or 'block', "
                             f"got {queue_policy!r}")
        if max_queue is not None and int(max_queue) < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if max_step_retries < 0:
            raise ValueError("max_step_retries must be >= 0")
        if step_timeout_s is not None and step_timeout_s <= 0:
            raise ValueError("step_timeout_s must be > 0")
        if max_preemptions < 0:
            raise ValueError("max_preemptions must be >= 0")
        if priority_aging_s is not None and priority_aging_s <= 0:
            raise ValueError("priority_aging_s must be > 0 (or None to "
                             "disable aging)")
        # the pool is built from what the model says it caches, not from
        # head counts read off its config
        spec = cache_spec_of(model)
        self.cache_spec = spec
        self.buckets = self._make_buckets()
        kv_heads = spec.sides[0][0]
        if cache_dtype is None:
            params = model.parameters()
            cache_dtype = params[0].dtype if params else "float32"
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout={kv_layout!r}: the contiguous layout was "
                f"removed and the cache is always paged (drop the argument)")
        #: the statement is by layer: a pool a group of layers
        by_layer = bool(spec.layer_groups)
        keeps_state = any(g.state for g in spec.groups)
        if by_layer or spec.kind in ("latent", "indexed", "windowed"):
            # one vector a token has no per-KV-head axis to shard by, the
            # three-sided pool's indexer side has none either, a second
            # group's tables and allocator are not placed on a mesh, and
            # none has a form of the verify window
            form = "state" if keeps_state else \
                "by-layer" if by_layer else spec.kind
            caches, no_mesh = {
                "state": ("keeps a state of fixed size a slot in some "
                          "layers, snapshots of it for the prefix cache",
                          "the state and its snapshot pool are not sharded"),
                "by-layer": ("caches K and V by groups of layers, some only "
                             "inside a window",
                             "the groups' tables are not sharded"),
                "latent": ("caches one latent vector a token",
                           "the latent pool has no kv_heads axis to shard"),
                "indexed": ("caches K, V and an indexer key a token",
                            "the indexed pool is not sharded"),
                "windowed": ("caches an exact window and chunk summaries",
                             "the summary group is not sharded")}[form]
            refused = [what for what, asked in (
                (f"a serving mesh of more than one device ({no_mesh})",
                 mesh is not None and mesh.size > 1),
                (f"speculation= (the verify window has no {form} form)",
                 speculation is not None)) if asked]
            if refused:
                raise ValueError(
                    f"{type(model).__name__} {caches} and cannot serve "
                    f"with " + "; ".join(refused))
        self.kernel = kernel
        self.block_size = int(block_size)
        self.prefix_lookup_timeout_s = float(prefix_lookup_timeout_s)
        if self.min_bucket % self.block_size != 0:
            raise ValueError(
                f"block_size {self.block_size} must divide "
                f"min_bucket {self.min_bucket} (so every prefill "
                f"bucket is whole blocks)")
        if self.max_seq % self.block_size != 0:
            raise ValueError(
                f"block_size {self.block_size} must divide "
                f"max_seq {self.max_seq}")
        pool = dict(num_slots=self.num_slots, num_layers=spec.num_layers,
                    max_seq=self.max_seq, sides=spec.sides, dtype=cache_dtype,
                    block_size=self.block_size, num_blocks=num_kv_blocks,
                    kernel=self.kernel)
        if num_window_blocks is not None and not by_layer:
            raise ValueError(
                f"num_window_blocks: {type(model).__name__} states no "
                f"group that keeps a window")
        if num_state_snapshots is not None and not keeps_state:
            raise ValueError(
                f"num_state_snapshots: {type(model).__name__} states no "
                f"group that keeps state")
        if by_layer:
            # a pool a group of layers, each with its allocator and table;
            # retention, admission and the prefix hit are by group
            if num_summary_blocks is not None:
                raise ValueError(
                    f"num_summary_blocks: {type(model).__name__} keeps no "
                    f"summary group")
            sizes = [num_state_snapshots if g.state else
                     num_window_blocks if g.window else num_kv_blocks
                     for g in spec.groups]
            self.cache = GroupedKVCache(
                spec.groups, num_slots=self.num_slots, max_seq=self.max_seq,
                dtype=cache_dtype, block_size=self.block_size,
                num_blocks=sizes, kernel=self.kernel,
                max_tail=self.buckets[-1])
            self.prefix_cache = (GroupedPrefixCache(self.cache)
                                 if enable_prefix_cache else None)
            #: what the step span reports of the groups without asking them
            self._group_window = max(g.window for g in spec.groups)
            self._group_blocks = [p.num_blocks - p.allocator.reserved
                                  for p in self.cache.pools]
        elif spec.kind == "windowed":
            # a second group beside the K/V pool: a window's summaries a
            # block, its own allocator and table, and a prefix cache that
            # hits by whole windows first
            self.cache = WindowedKVCache(
                window=spec.window, chunk=spec.chunk,
                num_summary_blocks=num_summary_blocks, **pool)
            self.prefix_cache = (WindowedPrefixCache(self.cache)
                                 if enable_prefix_cache else None)
        else:
            if num_summary_blocks is not None:
                raise ValueError(
                    f"num_summary_blocks: {type(model).__name__} keeps no "
                    f"summary group")
            self.cache = PagedKVCache(**pool)
            self.prefix_cache = (
                PrefixCache(self.cache.allocator, self.block_size)
                if enable_prefix_cache else None)
        self.name = name or f"engine-{next(_engine_counter)}"
        self.metrics = ServingMetrics(self.name, num_slots=self.num_slots)
        self.metrics.health_cb = self.health
        self.metrics.paging_cb = self._paging_snapshot
        self.queue: deque = deque()
        self.running: Dict[int, Request] = {}
        self.free_slots: List[int] = list(range(self.num_slots))
        # constrained decoding (opt-in, docs/SERVING.md "Multi-tenant
        # serving"): stacked per-grammar automaton tables the sampler
        # masks logits with in-graph; None = no grammar lanes
        self.grammar_table = None
        if grammars is not None:
            from .grammar import GrammarTable

            self.grammar_table = (
                grammars if isinstance(grammars, GrammarTable)
                else GrammarTable(cfg.vocab_size, grammars))
        # on-device sampling state: per-slot params/key/token lanes,
        # lifted into the compiled steps like KV cache state — the token
        # lane IS the next decode step's input ids (no host round-trip)
        self.sampler = DeviceSampler(self.num_slots,
                                     grammar=self.grammar_table)
        # multi-LoRA serving (opt-in, docs/SERVING.md "Multi-tenant
        # serving"): stacked per-target adapter lanes + the per-slot
        # adapter-id lane, hooked into every Column/Row parallel linear;
        # None attaches no hooks (trace byte-identical to pre-tenancy)
        self.adapter_pool = None
        if adapters is not None:
            from .adapters import AdapterConfig, AdapterPool

            acfg = (adapters if isinstance(adapters, AdapterConfig)
                    else AdapterConfig(**dict(adapters)))
            self.adapter_pool = AdapterPool(
                self.model, self.num_slots,
                max_adapters=acfg.max_adapters, rank=acfg.rank,
                dtype=cache_dtype)
        # speculative decoding (opt-in, docs/SERVING.md "Speculative
        # decoding"): the draft model + its KV pool + proposal lanes;
        # None keeps the plain one-token decode loop
        self.spec = None
        if speculation is not None:
            from .spec_decode import SpecState

            self.spec = SpecState(self, speculation)
            self.metrics.spec_cb = self.spec.snapshot
        # tensor-parallel sharded serving (docs/SERVING.md "Sharded
        # serving"): weights shard over the `model` mesh axis via their
        # Megatron-TP specs, the KV pool by kv_heads (GQA groups stay
        # shard-local), the sampler lanes / block tables / lengths
        # replicate — one logical decision stream drives all shards.
        # None keeps today's single-chip engine byte for byte.
        self.shard = None
        if mesh is not None:
            from .sharding import ServingShard

            self.shard = ServingShard(
                mesh, kv_heads=kv_heads,
                num_heads=cfg.num_attention_heads)
            self.shard.place_model(self.model)
            self.shard.place_state(self)
        #: mesh-shape key ("model=2") journaled per admission and
        #: validated by recover() — None for an unsharded engine
        self.mesh_shape = self.shard.key if self.shard else None
        self._req_counter = itertools.count()
        self._prefill_fn = None
        self._decode_fn = None
        self._draft_prefill_fn = None
        self._draft_decode_fn = None
        self._verify_fn = None
        #: registered compiled program sets: ``(name, warm_fn)`` —
        #: ``warmup()`` drives every entry so no registered program
        #: (target OR draft/verify) is ever a cold compile in serving
        self._warmers: List[tuple] = []
        # resilience / lifecycle
        self.max_queue = None if max_queue is None else int(max_queue)
        self.queue_policy = queue_policy
        self.block_timeout_s = float(block_timeout_s)
        self.default_deadline_s = default_deadline_s
        self.max_step_retries = int(max_step_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.step_timeout_s = step_timeout_s
        # overload regime (priorities / preemption / shedding)
        self.max_preemptions = int(max_preemptions)
        self.priority_aging_s = None if priority_aging_s is None \
            else float(priority_aging_s)
        if fault_plan is None:
            from ..distributed.fault_tolerance.injection import \
                ServingFaultPlan

            fault_plan = ServingFaultPlan.from_env()
        self.fault_plan = fault_plan
        # sync-point sanitizer (docs/ANALYSIS.md): PADDLE_TPU_SANITIZE=1
        # counts+attributes host transfers per decode step, =strict also
        # forbids d2h inside the compiled step; None = zero overhead
        self.sanitizer = SyncSanitizer.from_env()
        # request-lifecycle tracer (docs/SERVING.md "Tracing & flight
        # recorder"): host-side span/event chain per request, no-op by
        # default; plus the always-on bounded flight recorder
        if tracer is None:
            tracer = RequestTracer.from_env() or NULL_TRACER
        self.tracer = tracer
        self.flight = FlightRecorder(flight_recorder_steps,
                                     name=self.name)
        # durable request journal (docs/SERVING.md "Durability & hot
        # swap"): a RequestJournal WAL of admission/token/terminal
        # records — None (default) journals nothing and costs nothing.
        # All journal writes are host-side file I/O outside the
        # hot-path dispatch functions.
        self.journal = journal
        #: weight version this engine serves (bumped by update_weights;
        #: every admission tags its request with the current value)
        self.model_version = int(model_version)
        self.state = "active"    # active | draining | stopped | unhealthy
        self._unhealthy_reason: Optional[str] = None
        #: devices this engine lost (simulated via the
        #: ``serving.shard_fail`` fault point, or recorded by host-side
        #: device-loss detection): read by the fleet's degraded rebuild
        #: to carve the surviving devices into a smaller viable mesh
        self.lost_devices: List = []
        self._consecutive_failures = 0
        self._step_counter = 0
        self._last_step_t: Optional[float] = None
        # what the ``engine.step`` span reports, kept as running
        # integers by ``_occupy`` / ``_vacate`` / ``_advance`` / ``_admit``
        # (never summed over requests for the span's sake)
        self._kv_tokens = 0          # cached tokens of the running slots
        self._admitted_step = 0      # prompts admitted by the current step
        self._step_span = None       # the open ``engine.step`` span
        #: tokens a work item of the paged decode kernel covers (set with
        #: the programs; None: no work list, no ``decode_chunks``)
        self._decode_chunk_tokens: Optional[int] = None
        #: work items a slot with so many cached tokens costs (set with it)
        self._decode_items = None
        #: decode-step load of the model's expert layers (empty for a
        #: model without experts: ``stats()`` then has no ``"moe"``)
        self._moe = {"tokens": 0, "assignments_held": 0,
                     "experts_touched": 0, "layer_steps": 0}
        #: decode-step selection of a model whose attention runs under an
        #: indexer (zeros otherwise: ``stats()`` then has no ``"sparse"``):
        #: tokens attended to and tokens cached, summed over running slots
        #: and averaged over the layers
        self._sparse = {"steps": 0, "selected": 0, "context": 0,
                        "prefills": 0, "prefill_context": 0,
                        "prefill_items_full": 0, "prefill_items_run": 0}
        #: decode steps by the way their program went through the
        #: sampler (``sampling.sampler_path``)
        self._sampler_steps = {"steps_greedy": 0, "steps_sampled": 0}
        #: a windowed cache's decode steps (rows the running slots attended
        #: to, a layer's count) and windows published, by where
        self._eva = {"steps": 0, "exact_rows": 0, "summary_rows": 0,
                     "context": 0, "windows_published_decode": 0,
                     "windows_published_prefill": 0, "prefill_windows": 0}
        #: a latent pool's prefills: the (query, key) pairs of the admitted
        #: prompts' real tokens, by the form the prefill program attends to
        #: them in
        self._latent = {"prefills": 0, "pairs_upprojected": 0,
                        "pairs_absorbed": 0}
        #: a cache stated by layer: decode steps by what their layers read
        #: (a layer's count of each kind, summed over the running slots) and
        #: blocks its window groups let go of, by where
        self._swa = {"steps": 0, "full_rows": 0, "window_rows": 0,
                     "context": 0, "blocks_released_decode": 0,
                     "blocks_released_prefill": 0, "prefill_items_full": 0,
                     "prefill_items_window": 0, "prefill_items_run": 0,
                     "prefill_tile_rows": 0, "prefill_real_rows": 0}
        #: a group that keeps state: slots whose state the decode steps
        #: rewrote, and prefills by the state they started from
        self._state = {"steps": 0, "slots": 0, "prefills": 0,
                       "prefills_restored": 0, "hit_tokens_given_up": 0,
                       "state_bytes_restored": 0,
                       "state_bytes_snapshotted": 0, "step_state_bytes": 0}
        #: the host's path of the admissions: walks over a prompt for its
        #: chain keys and staging programs issued (``serving/staging``)
        self._admission = {"admissions": 0, "key_passes": 0,
                           "staging_programs": 0}
        #: the one program that writes a slot's sampler lanes and table rows
        #: (built with the steps, after the state is placed)
        self._stager: Optional[SlotStager] = None
        self._publish_fn = None
        self._watchdog = None
        self._arm_counter = 0

    # -- compiled steps ----------------------------------------------------

    def _make_buckets(self) -> List[int]:
        # a cache with a group that keeps a window prefills a long prompt
        # in pieces (``_tail_end``): no tail is longer than its limit
        top = min(self.cache_spec.tail_limit or self.max_seq, self.max_seq)
        b, out = self.min_bucket, []
        while b < top:
            out.append(b)
            b *= 2
        out.append(top)
        return out

    def bucket_for(self, prompt_len: int) -> int:
        if prompt_len > self.max_seq:
            raise ValueError(f"prompt length {prompt_len} exceeds cache "
                             f"capacity max_seq={self.max_seq}")
        for b in self.buckets:
            if prompt_len <= b:
                return b
        return self.buckets[-1]

    def _build_steps(self) -> None:
        """Compile-cached prefill/decode programs.  Built lazily so the
        engine can be constructed before any backend is touched."""
        from .. import jit as jit_mod

        model, cache, sampler = self.model, self.cache, self.sampler
        pool = self.adapter_pool
        if self.spec is None:
            self._decode_chunk_tokens = cache.decode_chunk_tokens()
            self._decode_items = cache.decode_items_fn()

        def _prefill_rows(slot):
            # this prefill's slot selects its adapter lane: a [1] row id
            # read from the lifted id lane (data, never a trace constant)
            return jax.lax.dynamic_index_in_dim(
                pool.adapter_ids._value(),
                slot._value().astype(jnp.int32), axis=0, keepdims=True)

        def prefill_step(input_ids, slot, length, start):
            # tail-bucket prefill: tokens are the UNCACHED tail of the
            # prompt, sitting at absolute positions start..; the last
            # real token is at tail index (length - start - 1)
            ctx = PagedCacheContext(cache, "prefill", slot=slot,
                                    length=length, start=start)
            if pool is not None:
                pool.set_rows(_prefill_rows(slot))
            try:
                logits = model(input_ids, cache_ctx=ctx)
            finally:
                if pool is not None:
                    pool.clear_rows()
            cache.set_length(slot, length)
            arr = logits._value()                   # [1, S, V]
            last = ctx.last_logits(
                arr, (length._value() - start._value()).astype(
                    jnp.int32) - 1)
            # first token sampled on-device from the slot's staged
            # lanes; key + token lanes update in-program
            tok = sampler.sample_slot(slot._value(),
                                      last.astype(jnp.float32))
            return Tensor._wrap(tok)

        def decode_step(active):
            # input ids come from the sampler's device-side token lane
            # (the previous step's sampled tokens — no host round-trip);
            # the pool routes attention through the Pallas flash-decoding
            # kernel or the gathering oracle (``kernel=``)
            tokens = Tensor._wrap(sampler.tokens._value()[:, None])
            ctx = PagedCacheContext(cache, "decode", active=active)
            if pool is not None:
                # all slots decode at once: the full [slots] id lane
                pool.set_rows(pool.adapter_ids._value())
            try:
                logits = model(tokens, cache_ctx=ctx)
            finally:
                if pool is not None:
                    pool.clear_rows()
            cache.advance(active)
            toks = sampler.sample_all(
                logits._value()[:, -1, :].astype(jnp.float32),
                active._value())
            # a model with expert layers: their load rides behind the
            # tokens, in the one array the host pulls
            return Tensor._wrap(ctx.with_expert_counts(toks))

        def publish_step(slot, window):
            # a decode step closed ``window`` of ``slot``: its summaries from
            # its exact blocks into the slot's summary block, layer by layer
            for i, (phi, mu) in enumerate(model.summary_params()):
                cache.publish(i, slot, window, True, phi._value(),
                              mu._value())
            return Tensor._wrap(jnp.zeros((), jnp.int32))

        self._prefill_fn = jit_mod.to_static(prefill_step)
        self._stager = SlotStager(sampler.lanes(), cache.tables())
        self._warmers = [("prefill", self._warm_prefill)]
        if self.cache_spec.kind == "windowed":
            self._publish_fn = jit_mod.to_static(publish_step)
            self._warmers.append(("publish", self._warm_publish))
        if self.spec is None:
            self._decode_fn = jit_mod.to_static(decode_step)
            self._warmers.append(("decode", self._warm_decode))
        else:
            # round-based speculative serving replaces the plain decode
            # program entirely: draft prefill per bucket, ONE draft
            # decode (proposal column j is an argument), ONE verify
            self._draft_prefill_fn = jit_mod.to_static(
                self.spec.make_draft_prefill(self))
            self._draft_decode_fn = jit_mod.to_static(
                self.spec.make_draft_decode(self))
            self._verify_fn = jit_mod.to_static(
                self.spec.make_verify(self))
            self._warmers.extend([
                ("draft_prefill", self._warm_draft_prefill),
                ("draft_decode", self._warm_draft_decode),
                ("verify", self._warm_verify),
            ])

    # -- warmup routines (one per registered program set) ------------------

    def _warm_prefill(self, buckets) -> None:
        for b in buckets:
            ids = np.zeros((1, int(b)), dtype=np.int64)
            # dummy admission into slot 0: real block assignment so
            # the traced table reads see representative state, then
            # released — warmup registers nothing in the prefix cache
            if not self.cache.begin_sequence(0, [], 0, int(b)):
                raise RuntimeError(
                    f"warmup: pool of {self.cache.num_blocks} blocks "
                    f"cannot hold one bucket-{b} prefill")
            try:
                self._call_counted(
                    self._prefill_fn, to_tensor(ids),
                    to_tensor(np.int32(0)), to_tensor(np.int32(1)),
                    to_tensor(np.int32(0)))
            finally:
                self.cache.release_slot(0)

    def _warm_decode(self, buckets) -> None:
        idle = np.zeros((self.num_slots,), dtype=np.int32)
        self._call_counted(self._decode_fn, to_tensor(idle))
        # the host-side table and block-copy programs of a growing
        # sequence, which no warm-up prefill reaches
        self.cache.warm_host_programs()

    def _warm_publish(self, buckets) -> None:
        # slot 0's rows point at the scratch blocks of both groups
        self._call_counted(self._publish_fn, to_tensor(np.int32(0)),
                           to_tensor(np.int32(0)))

    def _warm_draft_prefill(self, buckets) -> None:
        for b in buckets:
            ids = np.zeros((1, int(b)), dtype=np.int64)
            self._call_counted(
                self._draft_prefill_fn, to_tensor(ids),
                to_tensor(np.int32(0)), to_tensor(np.int32(1)))

    def _warm_draft_decode(self, buckets) -> None:
        idle = np.zeros((self.num_slots,), dtype=np.int32)
        self._call_counted(self._draft_decode_fn, to_tensor(idle),
                           to_tensor(np.int32(0)))

    def _warm_verify(self, buckets) -> None:
        idle = np.zeros((self.num_slots,), dtype=np.int32)
        cap = np.ones((self.num_slots,), dtype=np.int32)
        self._call_counted(self._verify_fn, to_tensor(idle),
                           to_tensor(cap))

    def _call_counted(self, fn, *args):
        """Run a compiled step, feeding the executable cache's own state
        into the hit/miss counters (a new program in the cache == one XLA
        compile == one miss).

        This is the single choke point every compiled call (warmup AND
        serving) passes through, so it is also where a sharded engine
        installs its mesh as the global mesh: the model forwards'
        ``mark_sharding`` and the TP layers read it during tracing, and
        the save/restore keeps co-resident engines (fleet shard groups
        on disjoint device subsets) from seeing each other's mesh."""
        from contextlib import nullcontext

        from ..core.autograd import no_grad

        mesh_ctx = (self.shard.context() if self.shard is not None
                    else nullcontext())
        before = len(fn.program_cache)
        with mesh_ctx, no_grad():
            out = fn(*args)
        self.metrics.on_compile(miss=len(fn.program_cache) > before)
        return out

    # -- resilience plumbing -----------------------------------------------

    def _fault(self, point: str) -> None:
        if self.fault_plan is not None and self.fault_plan.armed:
            self.fault_plan.check(point)

    def _mark_wedged(self) -> None:
        # runs on the watchdog thread; the stalled call may still return
        # later, but the engine is permanently visible as unhealthy
        self._unhealthy_reason = (
            f"step watchdog fired: no step completion within "
            f"{self.step_timeout_s}s (stacks dumped to stderr)")
        self.state = "unhealthy"
        # post-mortem: freeze the last-N-steps ring while it still shows
        # the lead-up (safe from this thread — the scheduler is stalled)
        self.flight.dump(self._unhealthy_reason)
        self.tracer.on_unhealthy(self.name, self._unhealthy_reason)

    def _mark_shard_lost(self, reason) -> None:
        """Device loss on a sharded engine (the ``serving.shard_fail``
        fault point): deterministically "lose" the highest-index device
        of this engine's mesh, record it in ``lost_devices`` for the
        fleet's degraded rebuild, and go sticky-unhealthy exactly like a
        watchdog wedge — ejection, flight dump, and supervision all
        reuse the existing unhealthy machinery."""
        lost = list(self.shard.mesh.devices.flat)[-1]
        self.lost_devices = [lost]
        self._unhealthy_reason = (
            f"shard failure: lost device {lost} of mesh "
            f"{self.mesh_shape!r} ({reason})")
        self.state = "unhealthy"
        self.flight.dump(self._unhealthy_reason)
        self.tracer.on_unhealthy(self.name, self._unhealthy_reason)

    def _arm_watchdog(self) -> None:
        if self.step_timeout_s is None:
            return
        if self._watchdog is None:
            from ..distributed.fault_tolerance.watchdog import StepWatchdog

            # the watchdog thread must not pin the engine (model + KV
            # cache): route on_timeout through a weakref, and let the
            # thread exit on its own if the engine is GC'd without
            # drain()/shutdown() (Event.set is safe in a finalizer;
            # joining is not)
            wref = weakref.ref(self)

            def _on_timeout():
                eng = wref()
                if eng is not None:
                    eng._mark_wedged()

            self._watchdog = StepWatchdog(
                self.step_timeout_s, hard_exit=False,
                on_timeout=_on_timeout)
            self._watchdog.start()
            weakref.finalize(self, self._watchdog.request_stop)
        self._arm_counter += 1
        self._watchdog.notify(self._arm_counter)

    def _disarm_watchdog(self) -> None:
        if self._watchdog is not None:
            self._watchdog.pause()

    def _step_call(self, point: str, fn, *args, span=None):
        """One compiled step with watchdog arming, fault injection, and a
        bounded retry.  Retry is state-safe: ``jit`` writes cache state
        back only after a call returns, so a failed attempt left the KV
        cache and lengths untouched.  ``span`` (the caller's dispatch
        span) is told how many attempts the call took."""
        last_err = None
        for attempt in range(self.max_step_retries + 1):
            if attempt:
                self.metrics.on_retry(point)
                time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))
            try:
                self._arm_watchdog()
                try:
                    self._fault(point)
                    out = self._call_counted(fn, *args)
                finally:
                    self._disarm_watchdog()
                self._consecutive_failures = 0
                if span is not None:
                    span.attrs["attempts"] = \
                        span.attrs.get("attempts", 0) + attempt + 1
                return out
            except Exception as e:       # noqa: BLE001 — isolated upstream
                last_err = e
                self._consecutive_failures += 1
                self.metrics.on_step_failure(point)
        raise last_err

    # -- public API --------------------------------------------------------

    @staticmethod
    def resolve_model(config):
        """Turn anything ``from_config`` accepts into a model Layer: a
        ready Layer passes through; a ``GPTConfig``/``LlamaConfig`` or a
        registry name (``"gpt:tiny"``, ``"llama:llama2-7b"``) builds the
        model.  Shared with ``serving.router.Fleet``, which builds ONE
        model and fans it across replicas."""
        from ..nn.layer_base import Layer
        from ..models import (
            GPT_CONFIGS, GPTConfig, GPTForCausalLM,
            LLAMA_CONFIGS, LlamaConfig, LlamaForCausalLM,
            DeepseekV3Config, DeepseekV3ForCausalLM,
        )

        if isinstance(config, Layer):
            return config
        if isinstance(config, GPTConfig):
            return GPTForCausalLM(config)
        if isinstance(config, LlamaConfig):
            return LlamaForCausalLM(config)
        if isinstance(config, DeepseekV3Config):
            return DeepseekV3ForCausalLM(config)
        if isinstance(config, str):
            family, _, which = config.partition(":")
            reg = {"gpt": (GPT_CONFIGS, GPTForCausalLM),
                   "llama": (LLAMA_CONFIGS, LlamaForCausalLM)}.get(family)
            if reg is None or (which or "tiny") not in reg[0]:
                raise KeyError(
                    f"unknown model spec {config!r}; want "
                    f"'gpt:<{'|'.join(GPT_CONFIGS)}>' or "
                    f"'llama:<{'|'.join(LLAMA_CONFIGS)}>'")
            cfgs, cls_ = reg
            return cls_(cfgs[which or "tiny"]())
        raise TypeError(
            f"Engine.from_config: unsupported config {type(config).__name__}"
            " — pass a GPTConfig/LlamaConfig, a 'family:size' name, or a "
            "model Layer.  (jit.save artifacts have no cache-aware forward;"
            " serve those through inference.Predictor instead.)")

    @classmethod
    def from_config(cls, config, **engine_kwargs) -> "Engine":
        """Predictor-compatible entry: build an Engine from a model config
        (``GPTConfig``/``LlamaConfig``), a registry name (``"gpt:tiny"``,
        ``"llama:llama2-7b"``), or a ready model Layer."""
        return cls(cls.resolve_model(config), **engine_kwargs)

    def _validate(self, req: Request) -> Optional[str]:
        """Enqueue-time validation: a malformed request is ``rejected``
        here, never admitted (where a failure would waste a prefill)."""
        if req.prompt_ids.size == 0:
            return "empty prompt"
        if req.prompt_ids.size > self.max_seq:
            return (f"prompt length {req.prompt_ids.size} exceeds "
                    f"max_seq={self.max_seq}")
        if req.max_new_tokens < 1:
            return f"max_new_tokens must be >= 1, got {req.max_new_tokens}"
        if req.deadline_s is not None and req.deadline_s <= 0:
            return f"deadline_s must be > 0, got {req.deadline_s}"
        # worst case (no prefix hit) the prompt prefills a full bucket
        # of fresh blocks; a prompt that can never fit the pool is
        # rejected up front instead of deferring forever
        need = self.bucket_for(req.prompt_ids.size) // self.block_size
        if 0 < self.cache_spec.tail_limit < req.prompt_ids.size:
            # a prompt prefilled in pieces (``_tail_end``): a piece's
            # bucket, and the next piece's beside it
            need *= 2
        usable = self.cache.usable_blocks()
        if need > usable:
            return (f"prompt needs {need} KV blocks "
                    f"(bucket {self.bucket_for(req.prompt_ids.size)}, "
                    f"block_size {self.block_size}) but the pool "
                    f"holds {usable}")
        s = req.sampling
        if s.adapter is not None:
            if self.adapter_pool is None:
                return (f"sampling.adapter={s.adapter!r} but this engine "
                        "has no adapter pool (Engine(adapters=...))")
            try:
                self.adapter_pool.resolve(s.adapter)
            except KeyError as e:
                return e.args[0]
        if s.grammar is not None:
            if self.grammar_table is None:
                return (f"sampling.grammar={s.grammar!r} but this engine "
                        "has no grammar table (Engine(grammars=...))")
            try:
                spec = self.grammar_table.spec_of(s.grammar)
            except KeyError as e:
                return e.args[0]
            g_eos = getattr(spec, "eos_token_id", None)
            if (g_eos is not None and req.eos_token_id is not None
                    and req.eos_token_id != g_eos):
                return (f"grammar {s.grammar!r} terminates on eos token "
                        f"{g_eos} but the request sets "
                        f"eos_token_id={req.eos_token_id}")
        return None

    def _reject(self, req: Request, reason: str) -> None:
        req.state, req.error = "rejected", reason
        req.t_finish = time.perf_counter()
        self.metrics.on_reject()
        self.tracer.on_retired(req, self.name, "rejected", reason)

    @staticmethod
    def _seed_for(req: Request) -> int:
        """The request's sampling seed, reconstructible: every admission
        (first and preempt-resume alike) re-seeds the slot's device key
        lane with this value, so seeded sampling replays bitwise
        deterministically (greedy ignores the key stream)."""
        return (req.sampling.seed if req.sampling.seed is not None
                else (req.request_id + 1) * 7919)

    def add_request(self, prompt_ids: Sequence[int], *,
                    max_new_tokens: int = 16,
                    sampling: Optional[SamplingParams] = None,
                    temperature: Optional[float] = None,
                    eos_token_id: Optional[int] = None,
                    stream_cb: Optional[Callable] = None,
                    deadline_s: Optional[float] = None,
                    block_timeout_s: Optional[float] = None,
                    priority=PRIORITY_NORMAL) -> Request:
        """Enqueue a prompt; it is admitted into a slot by a later
        ``step()``.  Returns the live Request handle.

        Malformed requests are marked ``rejected`` and raise ``ValueError``
        (the rejected handle rides on the exception's ``.request``).  A
        full queue raises :class:`QueueFull` under the ``reject`` policy,
        or blocks (driving ``step()``) up to ``block_timeout_s`` under
        ``block``.  ``deadline_s`` is this request's wall-clock budget
        from enqueue (default: the engine's ``default_deadline_s``); a
        deadline-carrying request whose estimated queue wait already
        exceeds that budget is shed at admission (:class:`ShedReject`,
        with ``retry_after_s``) instead of being prefilled doomed.
        ``priority`` is the request's class (``"low"|"normal"|"high"`` or
        any int; higher serves first, may preempt strictly lower)."""
        prio = _as_priority(priority)
        if self.state != "active":
            raise EngineStopped(
                f"engine {self.name!r} is {self.state}: not admitting "
                "new requests")
        prompt = np.asarray(prompt_ids, dtype=np.int64).reshape(-1)
        if sampling is None:
            sampling = SamplingParams(temperature=temperature or 0.0)
        req = Request(prompt_ids=prompt, max_new_tokens=int(max_new_tokens),
                      sampling=sampling, eos_token_id=eos_token_id,
                      stream_cb=stream_cb,
                      deadline_s=(deadline_s if deadline_s is not None
                                  else self.default_deadline_s),
                      priority=prio,
                      request_id=next(self._req_counter))
        # tenant label for SLO accounting (adapter > grammar > base)
        req.tenant = (sampling.adapter if sampling.adapter is not None
                      else (f"grammar:{sampling.grammar}"
                            if sampling.grammar is not None else "base"))
        if (sampling.grammar is not None and req.eos_token_id is None
                and self.grammar_table is not None):
            # a grammar terminates on ITS eos token; default the
            # request's stop condition to match (mismatch is rejected
            # in _validate)
            try:
                spec = self.grammar_table.spec_of(sampling.grammar)
                req.eos_token_id = getattr(spec, "eos_token_id", None)
            except KeyError:
                pass                     # unknown grammar → _validate
        req.t_enqueue = time.perf_counter()
        origin_wall = None
        jr = self.journal
        if jr is not None:
            # durable identity, consumed BEFORE the admission-control
            # checks: the router/recovery may have armed an adoption
            # (fleet-scoped id, recovered flag), and a recovered replay
            # must be exempt from SLO shedding below — it was accepted
            # once already, before the crash.  Otherwise the id is
            # engine-scoped, uniquified across process restarts by the
            # journal's boot marker.
            pend = jr.take_pending()
            if pend is not None:
                req.journal_id, req.recovered, origin_wall = pend
            else:
                req.journal_id = \
                    f"{self.name}:b{jr.boot}:r{req.request_id}"
        problem = self._validate(req)
        if problem is not None:
            self._reject(req, problem)
            err = ValueError(problem)
            err.request = req
            raise err
        if sampling.adapter is not None:
            # pin the adapter version at enqueue: unload/hot-swap of
            # this name fails the request instead of serving a torn
            # hybrid, and recovery refuses any other version
            req.adapter_version = self.adapter_pool.resolve(
                sampling.adapter)[1]
        wait = None if req.recovered else self._shed_wait_s(req)
        if wait is not None:
            depth = len(self.queue)
            msg = (f"shed: estimated queue wait {wait:.3f}s exceeds "
                   f"deadline {req.deadline_s}s (depth={depth}, "
                   f"retry_after_s={wait:.3f})")
            req.error_ctx = {"depth": depth,
                             "retry_after_s": round(wait, 3)}
            self.metrics.on_shed()
            self.tracer.on_shed(req, self.name, wait)
            self._reject(req, msg)
            err = ShedReject(msg, depth, retry_after_s=round(wait, 3))
            err.request = req
            raise err
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.queue_policy == "block":
                budget = self.block_timeout_s if block_timeout_s is None \
                    else float(block_timeout_s)
                t_end = time.perf_counter() + budget
                while len(self.queue) >= self.max_queue:
                    if time.perf_counter() >= t_end:
                        break
                    self.step()          # drain: admit/decode in-flight work
            if len(self.queue) >= self.max_queue:
                depth = len(self.queue)
                retry = round(self.estimate_queue_wait_s(req.priority), 3)
                msg = (f"queue full: {depth} >= max_queue={self.max_queue} "
                       f"(policy={self.queue_policy}, "
                       f"retry_after_s={retry})")
                req.error_ctx = {"depth": depth, "retry_after_s": retry}
                self._reject(req, msg)
                err = QueueFull(msg, depth, retry_after_s=retry)
                err.request = req
                raise err
        req._engine = weakref.ref(self)
        if jr is not None:
            # WAL discipline: the admission record commits BEFORE the
            # request enters the queue.  A failing journal write (disk
            # full, closed file) must not leave the engine serving a
            # request its caller was told failed — reject the handle
            # and surface the storage error instead.
            s = req.sampling
            samp = {"temperature": s.temperature, "top_k": s.top_k,
                    "top_p": s.top_p, "seed": s.seed}
            # tenancy keys ride only when set: pre-tenancy records (and
            # base-tenant admissions) stay byte-identical
            if s.adapter is not None:
                samp["adapter"] = s.adapter
            if s.grammar is not None:
                samp["grammar"] = s.grammar
            try:
                jr.record_admission(
                    req.journal_id, prompt_ids=req.prompt_ids,
                    sampling=samp,
                    seed_effective=self._seed_for(req),
                    priority=req.priority, deadline_s=req.deadline_s,
                    max_new_tokens=req.max_new_tokens,
                    eos_token_id=req.eos_token_id, engine=self.name,
                    model_version=self.model_version,
                    recovered=req.recovered,
                    mesh_shape=self.mesh_shape,
                    adapter_version=req.adapter_version)
            except Exception as e:       # noqa: BLE001 — storage failure
                req.journal_id = None    # nothing durable to audit
                self._reject(req, f"journal admission write failed: "
                                  f"{type(e).__name__}: {e}")
                try:
                    e.request = req      # the rejection-path convention
                except Exception:        # noqa: BLE001 — exotic exc type
                    pass
                raise
        self.queue.append(req)
        self.metrics.on_enqueue(len(self.queue))
        self.tracer.on_queued(req, self.name)
        if jr is not None and req.recovered:
            self.metrics.on_recovered()
            self.tracer.on_recovered(req, self.name, origin_wall,
                                     journal_id=req.journal_id)
        return req

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> dict:
        """Pre-compile EVERY registered compiled program set with dummy
        traffic, then reset all per-slot state — so live serving starts
        with a hot executable cache and zero steady-state misses.

        The registry (``_warmers``, built by ``_build_steps``) covers
        the target's prefill buckets and decode step AND, with
        speculation on, the draft model's prefill buckets, the draft
        decode step, and the verify step — so the first speculative
        round is never a cold compile (assert it via
        ``stats()["compile_cache"]``: the miss counter must not move
        after warmup)."""
        if self.running or self.queue:
            raise RuntimeError("warmup() must run before serving traffic "
                               "(it scribbles over slot 0 and resets all "
                               "slot lengths)")
        if self.state != "active":
            raise EngineStopped(f"engine {self.name!r} is {self.state}")
        if self._prefill_fn is None:
            self._build_steps()
        use = list(buckets or self.buckets)
        for _name, warm in self._warmers:
            warm(use)
        # the staging program, like the cache's own host programs: slot 0's
        # rows as they are, its lanes left alone (one shape, so one program
        # whatever an admission stages later)
        self._stager.stage(0, None, self.cache.table_rows(0))
        self.cache.reset()
        self.sampler.reset()             # warmup scribbled slot 0's lanes
        if self.adapter_pool is not None:
            self.adapter_pool.reset_slots()
        if self.spec is not None:
            self.spec.reset()
        if self.shard is not None:
            # the resets replaced the device arrays with fresh host
            # zeros — re-pin them to the mesh so serving's first step
            # sees the same shardings the warmup programs compiled for
            self.shard.place_state(self)
        return {"buckets": use,
                "programs": [name for name, _ in self._warmers],
                "compile_misses": self.metrics.compile_misses}

    # -- scheduling --------------------------------------------------------

    def _deadline_expired(self, req: Request, now: float) -> bool:
        return req.deadline_s is not None and \
            (now - req.t_enqueue) > req.deadline_s

    def _fail_deadline(self, req: Request) -> None:
        self.metrics.on_deadline()
        self._retire(req, "failed",
                     error=f"deadline of {req.deadline_s}s exceeded")

    # -- overload: priorities, preemption, shedding ------------------------

    def _effective_priority(self, req: Request, now: float) -> int:
        """Base priority class plus the deferral-aging boost (+1 class
        per ``priority_aging_s`` of queue wait) — the no-starvation
        ordering: sustained higher-priority arrivals cannot hold a
        waiting request back forever."""
        if self.priority_aging_s is None:
            return req.priority
        return req.priority + int(
            max(0.0, now - req.t_enqueue) / self.priority_aging_s)

    def _best_queued_index(self, now: float) -> Optional[int]:
        """Index of the next request to admit: highest effective
        priority, FIFO within a class (the first maximum wins, and the
        deque keeps arrival order)."""
        best_i, best_eff = None, None
        for i, q in enumerate(self.queue):
            eff = self._effective_priority(q, now)
            if best_eff is None or eff > best_eff:
                best_i, best_eff = i, eff
        return best_i

    def _best_preempting_candidate(self, now: float):
        """With every slot busy, the queued request that should preempt:
        highest effective priority among those for which a victim
        exists.  The effective head of the queue may hold NO preemption
        rights (aging grants queue position, never eviction — e.g. an
        aged low ahead of a fresh high over all-normal slots); it keeps
        its position for the next natural retirement while the
        entitled request evicts past it.  Returns
        ``(index, request, victim)`` or ``(None, None, None)``."""
        best, best_eff = (None, None, None), None
        for i, q in enumerate(self.queue):
            if q.done:
                continue
            eff = self._effective_priority(q, now)
            if best_eff is not None and eff <= best_eff:
                continue
            victim = self._pick_victim(q)
            if victim is not None:
                best, best_eff = (i, q, victim), eff
        return best

    def estimate_queue_wait_s(self,
                              priority: int = PRIORITY_NORMAL) -> float:
        """Estimated wall-clock wait before a fresh request of
        ``priority`` reaches a slot: the backlog it must wait behind
        (running requests' remaining token budgets plus queued requests
        at >= its effective priority) priced at the measured mean
        inter-token latency, spread over the decode batch width.

        Advisory and conservative by construction: a cold engine (no
        decode measurements yet) estimates 0.0 — admission never sheds
        on a guess — and a request the free slots can absorb this step
        waits 0.0.  Shared by SLO shedding and the fleet router's
        ``retry_after_s``."""
        if not self.metrics.itl_s:
            return 0.0
        now = time.perf_counter()
        queued_ahead = [q for q in self.queue
                        if self._effective_priority(q, now)
                        >= int(priority)]
        if len(queued_ahead) < len(self.free_slots):
            return 0.0
        itl = sum(self.metrics.itl_s) / len(self.metrics.itl_s)
        tokens = sum(max(0, r.max_new_tokens - len(r.output_ids))
                     for r in self.running.values())
        tokens += sum(q.max_new_tokens for q in queued_ahead)
        return tokens * itl / max(self.num_slots, 1)

    def _shed_wait_s(self, req: Request) -> Optional[float]:
        """SLO shed decision at admission: the estimated queue wait when
        it already exceeds the request's wall-clock deadline (the
        request could not finish in time even if decode were free), else
        None.  Deadline-less requests are never shed.  Preemption
        entitlement trumps the backlog estimate: a request that would
        evict its way into a slot on its first scheduling pass does not
        wait behind the running backlog, so it is never shed on it.  A
        queued request contends for that entitlement only if it could
        WIN the preemption pass — effective priority at >= this class
        AND a victim of its own (mirroring
        ``_best_preempting_candidate``: an aged victimless head never
        blocks the entitled preemptor there, so it must not force a
        shed here either)."""
        if req.deadline_s is None:
            return None
        now = time.perf_counter()
        if self._pick_victim(req) is not None and not any(
                not q.done and self._effective_priority(q, now)
                >= req.priority and self._pick_victim(q) is not None
                for q in self.queue):
            return None
        wait = self.estimate_queue_wait_s(req.priority)
        return wait if wait > req.deadline_s else None

    def _pick_victim(self, candidate: Request) -> Optional[Request]:
        """The preemption policy: among running requests of a strictly
        LOWER base priority class than the candidate's (aging never
        grants preemption rights — equal-priority workloads must not
        churn) with eviction budget left, evict the lowest class first,
        least progress (fewest emitted tokens) next, youngest last —
        minimizing the decode work thrown away."""
        if self.max_preemptions <= 0:
            return None
        cands = [r for r in self.running.values()
                 if r.priority < candidate.priority
                 and r.preemptions < self.max_preemptions]
        if not cands:
            return None
        return min(cands, key=lambda r: (r.priority, len(r.output_ids),
                                         -r.request_id))

    def _preempt(self, victim: Request) -> None:
        """Evict a running request so a higher-priority admission can
        take its slot (or its blocks).  NOT a terminal
        transition — the victim requeues replay-from-prompt under the
        redispatch stream contract: ``preempted``/``preemptions`` set
        and ``output_ids`` reset BEFORE the replay's token 0, stream
        restarting from token 0 on resume.

        Resume is cheap by construction: the victim's whole prompt
        blocks are (re-)registered in the prefix cache *before* its slot
        releases, so the replay prefill hits the cached prefix and pays
        only the uncached tail bucket — reusing existing prefill
        executables, never adding a compile key."""
        slot = victim.slot
        if self.prefix_cache is not None:
            try:
                self.prefix_cache.register(victim.prompt_ids,
                                           self.cache.owned_blocks(slot),
                                           salt=self._tenant_salt(victim),
                                           keys=victim._keys)
            except Exception:            # noqa: BLE001 — isolation boundary
                self.metrics.on_prefix_register_error()
        self._vacate(slot)
        if slot not in self.free_slots:
            self.free_slots.append(slot)
        try:
            self.cache.release_slot(slot)
        except Exception as e:           # noqa: BLE001 — accounting bug
            self._mark_block_corruption(
                f"release_slot({slot}) failed on preemption: "
                f"{type(e).__name__}: {e}")
        if self.spec is not None:
            # draft KV is never resumed — the replay-from-prompt resume
            # re-prefills it (draft state is deliberately not durable)
            self.spec.release_slot(slot)
        victim.slot = None
        victim.state = "queued"
        victim.preempted = True
        victim.preemptions += 1
        victim.output_ids = []
        victim.t_first_token = None
        victim._seq_len = 0
        victim._defers = 0
        # deterministic replay: the device key lane re-seeds from
        # _seed_for at re-admission (stage_slot), not here — the victim
        # holds no slot until then
        self.queue.append(victim)        # aging runs from its original
        self.metrics.on_preempt(len(self.queue))     # t_enqueue
        self.tracer.on_preempt(victim, self.name)
        if self.journal is not None and victim.journal_id is not None:
            # the journaled stream restarts too: tokens before this
            # record are superseded by the resume's replay from token 0
            self.journal.record_restart(victim.journal_id, "preempt")

    # -- the running set (and the integers the step span reports) ----------

    def _occupy(self, req: Request, seq_len: int) -> None:
        """``req`` starts running in its slot with ``seq_len`` cached
        tokens: the one place the running set grows."""
        req._seq_len = seq_len
        self.running[req.slot] = req
        self._kv_tokens += seq_len
        self._admitted_step += 1

    def _vacate(self, slot: int) -> None:
        """The slot stops running (retired or preempted): the one place
        the running set shrinks."""
        req = self.running.pop(slot, None)
        if req is not None:
            self._kv_tokens -= req._seq_len

    def _advance(self, req: Request, n: int = 1) -> None:
        """``n`` more tokens of ``req`` were written to the cache."""
        req._seq_len += n
        self._kv_tokens += n

    def _trace_id(self, req: Request) -> str:
        """The id a request's spans share: the request tracer's when one
        is armed (fleet-rooted under a router), else the engine's own."""
        tr = self.tracer
        return (tr._req_trace.get(req) if tr.enabled else None) \
            or f"{self.name}:r{req.request_id}"

    def _on_cancel(self, req: Request) -> None:
        """Queued requests leave immediately; running ones are retired at
        the next step boundary (their slot's cache state is untouched
        mid-step — retirement only changes argument values)."""
        if req.state != "queued":
            return
        try:
            self.queue.remove(req)
        except ValueError:
            # already claimed by the scheduler (popped for admission, or
            # reaped): leave the flag — _admit/_reap honor it.  Retiring
            # here would free a slot the scheduler just assigned.
            return
        self._retire(req, "cancelled")
        self.metrics.queue_depth = len(self.queue)

    def _reap(self, now: float) -> None:
        """Honor cancellations and deadlines before building this step's
        batches, for queued and running requests alike."""
        for req in list(self.queue):
            if not (req.done or req._cancel or
                    self._deadline_expired(req, now)):
                continue
            try:
                self.queue.remove(req)
            except ValueError:
                continue     # a concurrent cancel() already removed it
            if req.done:
                continue
            if req._cancel:
                self._retire(req, "cancelled")
            else:
                self._fail_deadline(req)
        for req in list(self.running.values()):
            if req._cancel:
                self._retire(req, "cancelled")
            elif self._deadline_expired(req, now):
                self._fail_deadline(req)
        self.metrics.queue_depth = len(self.queue)

    def _emit_token(self, req: Request, tok: int, now: float) -> bool:
        """Record one emitted token and run the stream callback.  A
        raising callback fails THIS request only: the error is recorded on
        the request and counted, never propagated into the batch."""
        if req.t_first_token is None:
            req.t_first_token = now
        req.output_ids.append(int(tok))
        if req.stream_cb is not None:
            try:
                self._fault("serving.stream_cb")
                req.stream_cb(int(tok), req)
            except Exception as e:       # noqa: BLE001 — isolation boundary
                self.metrics.on_callback_error()
                self._retire(req, "failed",
                             error=f"stream_cb raised: "
                                   f"{type(e).__name__}: {e}")
                return False
        return True

    def _tenant_salt(self, req: Request) -> bytes:
        """Prefix-cache tenant salt for ``req``'s adapter (``b""`` for
        the base tenant): folded into the chain-hash root so tenant KV
        never cross-hits across adapters or versions.  An
        unloaded-but-versioned name still salts uniquely, so a dying
        tenant cannot poison anyone else's lookups."""
        a = req.sampling.adapter
        if a is None or self.adapter_pool is None:
            return b""
        try:
            return self.adapter_pool.salt(a)
        except KeyError:
            v = self.adapter_pool.last_version(a)
            return f"{a}@v{v}#unloaded".encode()

    def _prefix_lookup(self, req: Request):
        """Longest cached prefix of the prompt, ``(n_tokens, block_ids)``.
        A raising or over-budget lookup degrades to a miss: the request
        still completes with a full prefill, the error is only counted
        (``paging.prefix_lookup_errors``), and no block was referenced.
        Hit-rate accounting happens in ``_paged_prefill`` AFTER the
        partial-hit cap, so the gauge only ever credits blocks that are
        actually reused — a discarded (raising/over-budget) result is
        recorded as a plain miss there."""
        req._hit_given_up = 0
        if self.prefix_cache is None:
            return 0, []
        t0 = time.perf_counter()
        try:
            self._fault("serving.prefix_lookup")
            hit_tokens, blocks = self.prefix_cache.lookup(
                req.prompt_ids, count=False,
                salt=self._tenant_salt(req), keys=req._keys)
            given_up = getattr(self.prefix_cache, "last_given_up", 0)
        except Exception:                # noqa: BLE001 — isolation boundary
            self.metrics.on_prefix_lookup_error()
            return 0, []
        if time.perf_counter() - t0 > self.prefix_lookup_timeout_s:
            # over-budget = degraded subsystem: discard the (late) result
            # and serve a deterministic plain miss, same as a raising
            # lookup (the stall itself is sunk cost — the lookup is
            # synchronous and cannot be pre-empted)
            self.metrics.on_prefix_lookup_error()
            return 0, []
        req._hit_given_up = given_up
        return hit_tokens, blocks

    def _prefill_call(self, req: Request, *args, start: int = 0,
                      end: Optional[int] = None):
        """One compiled prefill with the bounded retry; exhausted retries
        retire ``req`` as failed and return None (shared by both KV
        layouts so the retire semantics cannot diverge)."""
        try:
            with _spans.span("engine.prefill",
                             bucket=int(args[0].shape[1])) as sp:
                topk = self.cache_spec.topk
                if topk:
                    # the indexed path's condition, by the program's own
                    # rule: the tokens the tail is scored against (0: the
                    # dense path, a prompt of ``topk`` tokens or fewer)
                    n = len(req.prompt_ids)
                    scored = n if n > topk else 0
                    sp.attrs["dsa_context"] = scored
                    self._sparse["prefills"] += scored > 0
                    self._sparse["prefill_context"] += scored
                    self._note_indexed_prefill(sp, req.slot, start, end,
                                               scored > 0)
                if self.cache_spec.kind == "windowed":
                    self._note_prefill_windows(sp, start, end)
                if self.cache_spec.kind == "latent":
                    self._note_prefill_pairs(sp, start, end)
                if self.cache_spec.layer_groups:
                    self._note_group_prefill(sp, req.slot, start, end)
                    if self.cache.states:
                        self._note_state_prefill(sp, req, end - start)
                return self._step_call("serving.prefill",
                                       self._prefill_fn, *args, span=sp)
        except Exception as e:           # noqa: BLE001 — isolation boundary
            n = self.max_step_retries
            self._retire(req, "failed",
                         error=f"prefill failed after {n} "
                               f"retr{'y' if n == 1 else 'ies'}: "
                               f"{type(e).__name__}: {e}",
                         kind="replica")
            return None

    def _tail_end(self, start: int, L: int) -> int:
        """Where the prefill program that starts at ``start`` ends: the
        prompt's end — or, for a windowed cache and a tail longer than a
        window, the end of ``start``'s window.  Such a prompt is prefilled a
        window at a time: each program closes its window, publishes it and
        lets its exact blocks go before the next one starts, so a cold
        prompt of any length holds two windows of exact blocks at most and
        takes no bucket above the window's.  A cache stated by layer with a
        group that keeps a window cuts a tail at its ``tail_limit``."""
        W = self.cache_spec.tail_limit
        if not W or L - start <= W:
            return L
        if self.cache_spec.kind == "windowed":
            return (start // W + 1) * W
        # a cache stated by layer: pieces of the limit, so that a group with
        # a window holds the tail's blocks and one window's before them
        return start + W

    def _stage(self, req: Request, lanes: bool = True, **attrs) -> None:
        """The one staging program: ``req``'s slot's table rows as the host
        has them now and, with ``lanes``, its sampling lanes (parameters and
        the key re-seeded from the request's seed: the compiled step samples
        the first token on-device from exactly this state, identically on
        first admission, preempt-resume and recovery replay)."""
        with _spans.span("engine.stage", programs=1, **attrs):
            self._stager.stage(
                req.slot,
                self.sampler.lane_rows(req.sampling, self._seed_for(req))
                if lanes else None,
                self.cache.table_rows(req.slot))
        self._admission["staging_programs"] += 1

    def _paged_prefill(self, req: Request, L: int):
        """Paged admission up to the last prefill's dispatch: prefix lookup,
        block assignment, the slot staged by one program, tail-bucket
        prefill (of a prompt prefilled in pieces: a staging program and a
        prefill a piece, ``_tail_end``).  What an admission owes its prompt
        afterwards — the registration, the blocks behind a window — waits
        for the first token (``_register_prompt``).
        Returns ``(status, first_token, bucket, prefix_hit)``
        with status ``"ok" | "deferred" | "failed"`` (``deferred`` = the
        pool cannot supply the tail blocks right now and the slot was
        left untouched; ``failed`` = the request was already retired);
        ``first_token`` is the on-device-sampled first token (a scalar
        int32 device handle); ``prefix_hit`` is the reused prefix length
        in tokens."""
        with _spans.span("engine.prefix_lookup") as sp:
            P, bucket, ok = self._assign_blocks(req, L)
            sp.attrs["hit_tokens"] = P
        if not ok:
            return "deferred", None, bucket, P
        self._stage(req, piece=0)
        start, piece = P, 0
        while True:
            end = self._tail_end(start, L)
            ids = np.zeros((1, bucket), dtype=np.int64)
            ids[0, :end - start] = req.prompt_ids[start:end]
            last = self._prefill_call(
                req, to_tensor(ids), to_tensor(np.int32(req.slot)),
                to_tensor(np.int32(end)), to_tensor(np.int32(start)),
                start=start, end=end)
            if last is None:
                return "failed", None, bucket, P
            if end == L:
                return "ok", last, bucket, P
            # the next piece.  What the piece before let go of goes first:
            # the windows it closed were published inside its program (their
            # exact blocks go before the next piece takes its own), and a
            # group that keeps a window lets the blocks behind the next
            # query's window go, now that the tail's first query has read
            # them.  Then the piece's own blocks, and one program for all of
            # it and the slot's lanes: its first token is sampled from the
            # lanes as staged at admission, whatever the pieces before drew
            if self.cache_spec.kind == "windowed":
                self.cache.release_windows(req.slot, end, write=False)
            self._swa["blocks_released_prefill"] += \
                self.cache.release_behind(req.slot, end, write=False)
            start, bucket = end, self.bucket_for(
                self._tail_end(end, L) - end)
            if not self.cache.extend_tail(req.slot, start, bucket,
                                          write=False):
                self._retire(req, "failed",
                             error="KV block pool exhausted: no blocks for "
                                   f"the prompt's piece at {start}")
                return "failed", None, bucket, P
            piece += 1
            self._stage(req, piece=piece)

    def _register_prompt(self, req: Request, L: int, P: int) -> None:
        """What an admission owes its prompt once the first token is out:
        the blocks its last piece let go of, the prompt's whole blocks made
        hittable by later requests (hit blocks are refreshed, new full tail
        blocks registered), then the blocks behind the window of the
        sequence's next query, and one staging program for the rows that
        changed (the lanes left as the prefill advanced them)."""
        slot, bs = req.slot, self.block_size
        with _spans.span("engine.register") as sp:
            released = 0
            if self.cache_spec.kind == "windowed":
                # the windows the last piece closed: before the registration
                released += self.cache.release_windows(slot, L, write=False)
            # a group that keeps a window: only as far as the longest hit of
            # this very prompt (a resume's) would read, until the prompt is
            # registered
            behind = self.cache.release_behind(
                slot, (L - 1) // bs * bs, write=False)
            if self.prefix_cache is not None:
                try:
                    sp.attrs["entries"] = self.prefix_cache.register(
                        req.prompt_ids, self.cache.owned_blocks(slot),
                        salt=self._tenant_salt(req), keys=req._keys,
                        # a cache stated by layer: where this admission's
                        # hit ended (the window group drops a run moved past)
                        **({"hit_tokens": P} if self.cache_spec.layer_groups
                           else {}))
                except Exception:        # noqa: BLE001 — isolation boundary
                    self.metrics.on_prefix_register_error()
            behind += self.cache.release_behind(slot, L, write=False)
            self._swa["blocks_released_prefill"] += behind
            sp.attrs["released"] = released + behind
            if released + behind:
                self._stage(req, lanes=False)

    def _assign_blocks(self, req: Request, L: int):
        """The host half of a paged admission: prefix lookup, the
        partial-hit cap, and the slot's block assignment.  Returns
        ``(hit_tokens, bucket, assigned)``; ``assigned`` False = the pool
        cannot supply the tail blocks right now."""
        P, shared = self._prefix_lookup(req)
        bucket = self.bucket_for(self._tail_end(P, L) - P)
        # a PARTIAL hit can push prefix + padded tail past the slot's
        # block table (e.g. hit 8 of a 32-token prompt with buckets
        # {8,16,32}: 1 + 32/8 = 5 blocks on a 4-block table) — drop hit
        # blocks from the end until the padded tail fits; the remaining
        # hit is still a contiguous prefix
        while P and (P + bucket > self.max_seq):
            if self.cache_spec.layer_groups:
                # the end moves, and with it the blocks a window group needs
                P, shared = self.prefix_cache.lookup(
                    req.prompt_ids, count=False, salt=self._tenant_salt(req),
                    max_tokens=P - self.block_size, keys=req._keys)
                req._hit_given_up = self.prefix_cache.last_given_up
            else:
                P, shared = self.cache.shorten_hit(shared)
            bucket = self.bucket_for(self._tail_end(P, L) - P)
        if self.prefix_cache is not None and req._defers == 0:
            # one logical lookup per request (deferral retries re-look-up
            # for freshness but don't re-count), credited with only the
            # hit span that is ACTUALLY reused post-cap — discarded and
            # raising lookups land here as P == 0, i.e. a plain miss
            self.prefix_cache.record_lookup(L, P)
        extra = {}
        if self.cache_spec.kind == "windowed":
            # a summary block for every window the request's whole life can
            # close, and the exact group left with what the running
            # sequences (and this one) may still grow by: no sequence fails
            # for a block while decoding
            # (and, of a prompt prefilled in pieces, the next piece's window)
            bs = self.block_size
            extra = dict(total=L + req.max_new_tokens, reserve=sum(
                -(-(r.max_new_tokens - len(r.output_ids)) // bs) + 1
                for r in (*self.running.values(), req))
                + (self._tail_end(P, L) < L) * self.cache.window_blocks)
        elif self.cache_spec.layer_groups:
            # each group left with what the running sequences may still take
            # of it (a request is deferred, never failed, for want of either;
            # the sequence's own need is added where its blocks are known)
            needs = [self.cache.growth_needs(
                r.slot, r.prompt_ids.size + r.max_new_tokens)
                for r in self.running.values()]
            # (and, of a prompt prefilled in pieces, the next piece's bucket)
            more = 1 + (self._tail_end(P, L) < L) * (
                self.buckets[-1] // self.block_size)
            extra = dict(total=L + req.max_new_tokens,
                         reserve=[more + sum(n) for n in zip(*needs)]
                         if needs else [more] * len(self.cache.pools),
                         # a state group plans its snapshots to the piece's
                         # real end
                         end=self._tail_end(P, L))
        # (the host's lists only: the slot's rows go to the device with its
        # sampler lanes, in the one staging program)
        return P, bucket, self.cache.begin_sequence(
            req.slot, shared, P, bucket, write=False, **extra)

    def _admit(self, req: Request) -> Optional[bool]:
        """Prefill ``req`` into its pre-assigned slot.  Never raises for
        request-level problems — a prefill/sampling/callback failure fails
        this request only (``_retire`` reclaims the slot).  Returns False
        when admission must be deferred (no KV blocks free); the
        scheduler re-queues the request with its slot returned."""
        with _spans.span("engine.admit", trace=self._trace_id(req),
                         slot=req.slot,
                         prompt_tokens=int(req.prompt_ids.size)) as sp:
            sp.attrs["queue_wait_ms"] = round(
                1e3 * (sp.t0 - req.t_enqueue), 3)
            walked, staged = req._keys.passes, \
                self._admission["staging_programs"]
            deferred = self._admit_into_slot(req, sp)
            # walks over the prompt for its chain keys (0: a retry that
            # found them) and staging programs, the registration's too
            walked = req._keys.passes - walked
            self._admission["key_passes"] += walked
            sp.set(key_passes=walked, staging_programs=self._admission[
                "staging_programs"] - staged)
            # "admitted" was set where the slot was occupied; a request
            # retired before that reports how (failed | cancelled)
            sp.attrs.setdefault(
                "outcome", "deferred" if deferred is False else req.state)
            return deferred

    # tpulint: hot-path
    def _admit_into_slot(self, req: Request, sp) -> Optional[bool]:
        if req._cancel:                  # cancelled between pop and prefill
            self._retire(req, "cancelled")
            return None
        if self._deadline_expired(req, sp.t0):
            # expired while queued (possibly during an earlier admission
            # this very step): retire as a deadline failure WITHOUT
            # paying a compiled prefill for work that is already dead
            self._fail_deadline(req)
            return None
        L = int(req.prompt_ids.size)
        if self.adapter_pool is not None:
            # stage the slot's adapter lane id; a request whose adapter
            # vanished (unload) or moved on (hot-swap bumped the
            # version) between enqueue and admission fails here with
            # machine-readable context instead of decoding under the
            # wrong weights
            a = req.sampling.adapter
            try:
                if a is not None and req.adapter_version is not None:
                    _, v = self.adapter_pool.resolve(a)
                    if v != req.adapter_version:
                        raise KeyError(
                            f"adapter {a!r} was hot-swapped to v{v} "
                            f"(request pinned v{req.adapter_version})")
                self.adapter_pool.stage_slot(req.slot, a)
            except KeyError as e:
                req.error_ctx = {
                    "adapter": a,
                    "version": (req.adapter_version
                                if req.adapter_version is not None
                                else self.adapter_pool.last_version(a)),
                }
                self._retire(req, "failed", error=str(e.args[0]))
                return None
        status, tok_t, bucket, prefix_hit = self._paged_prefill(req, L)
        if status == "deferred":
            return False
        if status == "failed":
            return None
        if self.spec is not None and not self._spec_admit(req, L):
            return None
        now = time.perf_counter()
        self.metrics.prefill_time_s += now - sp.t0
        req.state, req.prefill_bucket = "running", bucket
        req.model_version = self.model_version
        self._occupy(req, L)
        sp.set(bucket=bucket, hit_tokens=prefix_hit, outcome="admitted")
        self.metrics.on_admit(bucket, L, len(self.queue))
        self.tracer.on_admitted(req, self.name, bucket, req.slot,
                                prefix_hit)
        self._admission["admissions"] += 1
        self._deliver_first_token(req, tok_t, now, prefix_hit)

    def _spec_admit(self, req: Request, L: int) -> bool:
        """Draft-side half of a speculating admission: stage the draft
        sampler lanes (params + salt-derived seed — identically on
        first admission, preempt-resume, and crash-recovery replay, the
        determinism contract) and prefill the prompt into the draft
        cache.  The draft always prefills its full-prompt bucket — it
        keeps no prefix cache; draft KV is cheap and deliberately not
        durable.  Failure retires the request (replica-implicated, like
        any compiled-step failure) and returns False."""
        self.spec.stage_slot(req.slot, req.sampling, self._seed_for(req))
        bucket = self.bucket_for(L)
        ids = np.zeros((1, bucket), dtype=np.int64)
        ids[0, :L] = req.prompt_ids
        try:
            self._step_call("serving.spec_draft_prefill",
                            self._draft_prefill_fn, to_tensor(ids),
                            to_tensor(np.int32(req.slot)),
                            to_tensor(np.int32(L)))
        except Exception as e:           # noqa: BLE001 — isolation boundary
            n = self.max_step_retries
            self._retire(req, "failed",
                         error=f"draft prefill failed after {n} "
                               f"retr{'y' if n == 1 else 'ies'}: "
                               f"{type(e).__name__}: {e}",
                         kind="replica")
            return False
        return True

    def _deliver_first_token(self, req: Request, tok_t, now: float,
                             prefix_hit: int) -> None:
        """Stream delivery of the admission's on-device-sampled first
        token.  The only host copy is the token scalar itself — a
        per-admission (never per-decode-step) pull, outside the
        hot-path dispatch functions.  The prompt's bookkeeping
        (``_register_prompt``) comes after the token is out and before the
        request can retire: a request done at its first token leaves its
        blocks hittable, and one whose callback failed registers
        nothing."""
        with _spans.span("engine.first_token"):
            tok = int(tok_t.numpy())
        if self.journal is not None and req.journal_id is not None:
            # journal BEFORE the user-visible emit: delivery is
            # at-least-once across a crash by contract
            self.journal.record_tokens(self.name, self._step_counter,
                                       {req.journal_id: tok})
        if not self._emit_token(req, tok, now):
            return
        self.metrics.on_first_token(req.ttft_s, tenant=req.tenant)
        self._register_prompt(req, int(req.prompt_ids.size), prefix_hit)
        if self._done_after_emit(req):
            self._retire(req)

    def _done_after_emit(self, req: Request) -> bool:
        if len(req.output_ids) >= req.max_new_tokens:
            return True
        if req.eos_token_id is not None and \
                req.output_ids[-1] == req.eos_token_id:
            return True
        # the NEXT decode would write at position _seq_len; the emitted
        # token itself still needs a cache line to attend from
        if req._seq_len + 1 > self.max_seq:
            return True
        return False

    def _retire(self, req: Request, state: str = "finished",
                error: Optional[str] = None,
                kind: Optional[str] = None) -> None:
        """THE single exit path: every terminal transition funnels here,
        so the slot is reclaimed exactly once on every outcome.
        Idempotent — a request already terminal is left untouched.
        ``kind`` tags who the failure implicates (``Request.error_kind``)
        so a fleet supervisor can tell replayable replica faults from
        request-fatal ones."""
        if req.done:
            return
        req.state = state
        if error is not None:
            req.error = error
        if kind is not None:
            req.error_kind = kind
        req.t_finish = time.perf_counter()
        slot = req.slot
        if slot is not None:
            self._vacate(slot)
            if slot not in self.free_slots:
                self.free_slots.append(slot)
            # drop the slot's block refs (idempotent); blocks also
            # registered in the prefix cache stay alive on its ref
            try:
                self.cache.release_slot(slot)
            except Exception as e:       # noqa: BLE001 — accounting bug
                self._mark_block_corruption(
                    f"release_slot({slot}) failed: "
                    f"{type(e).__name__}: {e}")
            if self.spec is not None:
                self.spec.release_slot(slot)
        if state == "finished":
            self.metrics.on_complete(tenant=req.tenant,
                                     n_tokens=len(req.output_ids))
        elif state == "cancelled":
            self.metrics.on_cancel()
        elif state == "failed":
            self.metrics.on_fail(tenant=req.tenant)
        self.tracer.on_retired(req, self.name, state, req.error)
        if self.journal is not None and req.journal_id is not None:
            # fleet-owned requests end their ATTEMPT here; the router's
            # exactly-once _finish writes the one FINAL end (mirror of
            # the tracer's final-event ownership)
            self.journal.record_end(
                req.journal_id, state,
                final=not self.journal.is_fleet_owned(req.journal_id),
                error=req.error, n_tokens=len(req.output_ids),
                engine=self.name)

    def _mark_block_corruption(self, reason: str) -> None:
        """A block-accounting violation is engine-fatal for trust (not
        for liveness): surface it sticky via health() instead of
        corrupting the pool silently."""
        if self.state != "unhealthy":
            self.state = "unhealthy"
            self._unhealthy_reason = f"KV block accounting: {reason}"
            self.flight.dump(self._unhealthy_reason)
            self.tracer.on_unhealthy(self.name, self._unhealthy_reason)

    def _prepare_decode_paged(self) -> None:
        """Host-side block maintenance before a paged decode step: each
        running slot's next write position must land on a block it owns
        exclusively — growing sequences get a fresh block, shared blocks
        are copied-on-extend.  A slot the pool cannot serve fails (the
        engine and its batch continue)."""
        for slot, req in list(self.running.items()):
            if self._publish_fn is not None \
                    and self.cache.windows_pending(slot, req._seq_len) > 0 \
                    and not self._publish_window(req):
                continue
            try:
                # a group that keeps a window lets the blocks behind the
                # next query's window go before it takes the next one
                self._swa["blocks_released_decode"] += \
                    self.cache.release_behind(slot, req._seq_len)
                ok = self.cache.ensure_capacity(slot, req._seq_len)
            except Exception as e:       # noqa: BLE001 — accounting bug
                self._mark_block_corruption(
                    f"ensure_capacity({slot}) failed: "
                    f"{type(e).__name__}: {e}")
                ok = False
            if not ok:
                self.tracer.on_block_pressure(req, self.name,
                                              kind="pool_exhausted",
                                              position=req._seq_len)
                self._retire(req, "failed",
                             error="KV block pool exhausted: no block "
                                   f"free for position {req._seq_len} "
                                   "(even after prefix-cache eviction)")

    def _publish_window(self, req: Request) -> bool:
        """The last decode step ended a window of ``req``'s slot: run the
        publishing program (the window's summaries from its exact blocks into
        the slot's summary block), then let the window's exact blocks go.
        A failing program fails the request, not the engine."""
        window = self.cache.published(req.slot)
        try:
            with _spans.span("engine.publish_window", slot=req.slot,
                             window=window) as sp:
                self._step_call("serving.publish", self._publish_fn,
                                to_tensor(np.int32(req.slot)),
                                to_tensor(np.int32(window)), span=sp)
                sp.attrs["exact_blocks_released"] = \
                    self.cache.release_windows(req.slot, req._seq_len)
        except Exception as e:           # noqa: BLE001 — isolation boundary
            self._retire(req, "failed", kind="replica",
                         error=f"publishing window {window} failed: "
                               f"{type(e).__name__}: {e}")
            return False
        self._eva["windows_published_decode"] += 1
        if self._step_span is not None:
            self._step_span.attrs["eva_windows_published"] = \
                self._step_span.attrs.get("eva_windows_published", 0) + 1
        return True

    def _note_prefill_windows(self, sp, start: int, L: int) -> None:
        """What the prefill of ``[start, L)`` does to the windows, by the
        program's own rule: the windows its rows lie in, and the ones it
        closes."""
        W = self.cache_spec.window
        rows_a_window = W // self.cache_spec.chunk
        touched = (L - 1) // W - start // W + 1
        closed = L // W - start // W
        # rows the tail's real queries attend to (each: its place in its
        # window, and the summary rows of the windows before it), and the
        # distinct rows behind them: the prefill kernel's needed work
        i = np.arange(start, L, dtype=np.int64)
        sp.set(eva_windows=touched, eva_windows_published=closed,
               eva_rows=int(np.sum(i % W + 1 + i // W * rows_a_window)),
               eva_keys=L - start // W * W + (L - 1) // W * rows_a_window)
        self._eva["prefill_windows"] += touched
        self._eva["windows_published_prefill"] += closed

    def _note_group_rows(self) -> None:
        """What this decode step's layers read, by the kernels' own rule and
        from the lengths the host knows: a layer that keeps all reads a
        slot's every token and the new one, a layer that keeps a window the
        last ``window`` of them — a layer's count of each kind, summed over
        the running slots — and each group's blocks that a live slot
        holds."""
        n = np.fromiter((r._seq_len + 1 for r in self.running.values()),
                        np.int64, len(self.running))
        full = int(n.sum())
        window = int(np.minimum(n, self._group_window).sum())
        sw = self._swa
        sw["steps"] += 1
        sw["full_rows"] += full
        sw["window_rows"] += window
        sw["context"] += full
        if self._step_span is not None:
            self._step_span.set(
                swa_full_rows=full, swa_window_rows=window, swa_context=full,
                swa_blocks_used=[p.allocator.used_blocks
                                 for p in self.cache.pools],
                swa_blocks=self._group_blocks)
        if self.cache.states:
            # the step rewrites the state of every running slot, in each of
            # the state groups' layers, and of no other
            first = self.cache.states[0]
            # what its recurrent side's kernels must read and write: the
            # running slots' state, once in and once out, a layer
            moved = len(self.running) * first.num_layers * 2 \
                * first.recurrent_nbytes()
            self._state["steps"] += 1
            self._state["slots"] += len(self.running)
            self._state["step_state_bytes"] += moved
            if self._step_span is not None:
                self._step_span.set(
                    state_slots=len(self.running), state_bytes=moved,
                    state_snapshots_used=first.rows_in_use(),
                    state_snapshots=first.num_blocks
                    - first.allocator.reserved)

    def _note_group_prefill(self, sp, slot: int, start: int,
                            L: int) -> None:
        """What the prefill of ``[start, L)`` must read in one layer of each
        kind: ``rows``, the keys its real queries attend to summed over the
        queries, and ``keys``, the distinct ones behind them."""
        W = self._group_window
        i = np.arange(start, L, dtype=np.int64)
        sp.set(swa_full_rows=int(np.sum(i + 1)), swa_full_keys=L,
               swa_window_rows=int(np.sum(np.minimum(i + 1, W))),
               swa_window_keys=L - max(0, start - W + 1))
        if self.cache_spec.kind == "latent":
            return          # the latent kernels' work is ``_note_prefill_pairs``
        # how the tail-prefill kernel gets there: a layer's work items by
        # kind of layer, and the rows it multiplies against the rows asked
        # for, from the kernel's own plan and list (nothing under
        # ``kernel="reference"``, which has neither)
        work = {"prefill_real_rows": L - start, "prefill_items_run": 0}
        for pool in self.cache.pools:
            got = pool.prefill_work(slot, sp.attrs["bucket"], start, L,
                                    self.config.num_attention_heads)
            if got is None:
                return
            work["prefill_items_window" if pool.kv_window
                 else "prefill_items_full"] = got[0]
            work.setdefault("prefill_tile_rows", got[1])
            work["prefill_items_run"] += got[2]
        sp.set(**work)
        for k, v in work.items():
            self._swa[k] += v

    def _note_indexed_prefill(self, sp, slot: int, start: int, L: int,
                              indexed: bool) -> None:
        """A layer's work items of the tail-prefill kernel in an indexed
        pool, under the selection (``indexed``) or dense, and those of them
        whose chunk is one run of the pool: under the names a cache stated by
        layer gives them (no layer here keeps a window: no
        ``prefill_items_window``)."""
        got = self.cache.prefill_work(slot, sp.attrs["bucket"], start, L,
                                      self.config.num_attention_heads,
                                      indexed=indexed)
        if got is None:                  # ``kernel="reference"``: no list
            return
        work = {"prefill_items_full": got[0], "prefill_items_run": got[2]}
        sp.set(**work)
        for k, v in work.items():
            self._sparse[k] += v

    def _note_state_prefill(self, sp, req: Request, tail: int) -> None:
        """What the program about to run does to the groups that keep state,
        from the plan its admission wrote: the snapshot row it starts from
        (0: the zeros, a cold prompt), the snapshots it writes, and the
        tokens this admission's hit gave up for want of a snapshot or of a
        window's blocks."""
        row, written = self.cache.planned[0]
        # what the restore and the snapshots move: a row weighs a slot's state
        weight = self.cache.states[0].slot_nbytes()
        moved = dict(state_bytes_restored=weight * (row > 0),
                     state_bytes_snapshotted=weight * written)
        sp.set(state_row=row, state_snapshots_written=written,
               state_hit_given_up=req._hit_given_up, **moved)
        chunk = self.cache.states[0].chunk
        if chunk:
            # the recurrence's scan over the tail's real tokens, in chunks
            sp.set(kda_tail_tokens=tail, kda_chunks=-(-tail // chunk))
        st = self._state
        st["prefills"] += 1
        st["prefills_restored"] += row > 0
        st["hit_tokens_given_up"] += req._hit_given_up
        for k, v in moved.items():
            st[k] += v

    def _note_prefill_pairs(self, sp, start: int, L: int) -> None:
        """The (query, key) pairs of the tail ``[start, L)``'s real tokens,
        by the latent prefill program's own rule: a key of the tail itself
        is in the program's hands and is attended to up-projected (the
        causal square), a cached one lies in the pool and is attended to
        absorbed (the rectangle under ``start``)."""
        n = L - start
        square, rectangle = n * (n + 1) // 2, n * start
        sp.set(latent_pairs_upprojected=square,
               latent_pairs_absorbed=rectangle)
        self._latent["prefills"] += 1
        self._latent["pairs_upprojected"] += square
        self._latent["pairs_absorbed"] += rectangle

    def _decode(self) -> None:
        """One decode step (or, with speculation on, one ROUND: k draft
        steps + one verify step).  The *dispatch* (``_decode_body`` /
        ``_spec_round_body``) runs under the sanitizer's counting window
        when armed (``PADDLE_TPU_SANITIZE``): every framework-level host
        coercion inside is counted and attributed to its source line —
        0.0 since ROADMAP item 2 moved sampling on-device (the PR 7
        baseline was the 1.0 per-step logits pull), and speculation
        keeps it 0.0 (proposals chain device-side, acceptance is
        in-graph).  Stream *delivery* — pulling the sampled ``[slots]``
        (or per-round ``[slots, k+2]``) int32 array for callbacks and
        stop checks — happens after the window closes: the next step's
        inputs already live on device (the sampler token lanes), so the
        pull is not on the dispatch critical path."""
        san = self.sanitizer
        if self.spec is not None:
            with (nullcontext() if san is None else san.decode_window()):
                res = self._spec_round_body()
            if res is not None:
                self._deliver_spec(*res)
            return
        with (nullcontext() if san is None else san.decode_window()):
            res = self._decode_body()
        if res is not None:
            self._deliver_tokens(*res)

    # tpulint: hot-path
    def _decode_body(self):
        """Dispatch one compiled decode step; device handles only — no
        d2h coercion belongs here (tpulint TPL106 enforces it, with ZERO
        suppressions since on-device sampling landed).  Returns
        ``(token_tensor, t0)`` or None (nothing ran / batch failed)."""
        with _spans.span("engine.prepare_decode"):
            self._prepare_decode_paged()
            if not self.running:
                return None
            active = np.zeros((self.num_slots,), dtype=np.int32)
            for slot in self.running:
                active[slot] = 1
            ct = self._decode_chunk_tokens
            if ct is not None and self._step_span is not None:
                # the kernel's work list, counted where the lengths are
                # known without asking the device
                self._step_span.attrs["decode_chunks"] = sum(
                    self._decode_items(req._seq_len)
                    for req in self.running.values())
            if self.cache_spec.layer_groups:
                self._note_group_rows()
            # the sampler's way through this step, by the rule its program
            # applies to the running slots' lanes
            path = sampler_path(
                req.sampling for req in self.running.values())
            self._sampler_steps["steps_" + path] += 1
            if self._step_span is not None:
                self._step_span.attrs["sampler_path"] = path
        san = self.sanitizer
        try:
            # the compiled step itself must not round-trip to host: the
            # sanitizer arms jax.transfer_guard_device_to_host around it
            # (log, or disallow in strict mode — backend-enforced on TPU)
            with (nullcontext() if san is None else san.compiled_guard()), \
                    _spans.span("engine.decode") as sp:
                out = self._step_call("serving.decode", self._decode_fn,
                                      to_tensor(active), span=sp)
        except Exception as e:           # noqa: BLE001 — isolation boundary
            # retry budget exhausted: every request in THIS batch is
            # implicated; fail them (reclaiming their slots) and keep the
            # engine alive for queued work
            # the guard's exact phrasing (jaxlib guard_lib), not a loose
            # "transfer" substring — ordinary step failures that happen
            # to mention buffers/transfers must not count as violations
            if san is not None and "device-to-host transfer" in str(e):
                san.guard_violations += 1
            msg = (f"decode step failed after {self.max_step_retries} "
                   f"retr{'y' if self.max_step_retries == 1 else 'ies'}: "
                   f"{type(e).__name__}: {e}")
            for req in list(self.running.values()):
                self._retire(req, "failed", error=msg, kind="replica")
            return None
        if san is not None:
            san.note_step()             # the compiled step actually ran
        return out, sp.t0

    def _deliver_tokens(self, out, t0: float) -> None:
        """Post-dispatch host half of a decode step: pull the sampled
        token ids (ONE tiny ``[slots] int32`` array — stream delivery
        and stop checks are host work by nature, and the pull sits
        outside both the sanitizer window and the hot-path dispatch),
        then run callbacks and retirement checks."""
        with _spans.span("engine.pull") as pull:
            toks = out.numpy()                   # [slots] int32
        now = pull.t1            # the step's latency runs on the spans' stamps
        extra = toks[self.num_slots:]   # what the model's layers counted
        if self.cache_spec.kind == "indexed":
            extra, sel = extra[:-3], extra[-3:]
            self._note_selection(sel)
        elif self.cache_spec.kind == "windowed":
            extra, rows = extra[:-4], extra[-4:]
            self._note_rows(rows)
        if len(extra):                           # a model with experts
            self._note_experts(extra)
        with _spans.span("engine.deliver") as sp:
            ran = len(self.running)
            self._deliver_pulled(toks, now, now - t0)
            sp.attrs["retired"] = ran - len(self.running)

    def _note_selection(self, counts) -> None:
        """The decode step's selection, as its program counted it (sums over
        the running slots and the layers): a layer's mean summed into
        ``stats()["sparse"]``, and the step's own on its span.  Equal
        numbers mean the dense path ran."""
        selected, context, layers = (int(c) for c in counts)
        selected, context = selected // layers, context // layers
        sp = self._sparse
        sp["steps"] += 1
        sp["selected"] += selected
        sp["context"] += context
        if self._step_span is not None:
            self._step_span.set(dsa_selected=selected, dsa_context=context)

    def _note_rows(self, counts) -> None:
        """The decode step's rows, as its program counted them (sums over the
        running slots and the layers): a layer's count summed into
        ``stats()["eva"]``, and the step's own on its span."""
        exact, summary, context, layers = (int(c) for c in counts)
        exact, summary, context = (n // layers
                                   for n in (exact, summary, context))
        ev = self._eva
        ev["steps"] += 1
        ev["exact_rows"] += exact
        ev["summary_rows"] += summary
        ev["context"] += context
        if self._step_span is not None:
            self._step_span.set(eva_exact_rows=exact,
                                eva_summary_rows=summary, eva_context=context)
            self._step_span.attrs.setdefault("eva_windows_published", 0)

    def _note_experts(self, counts) -> None:
        """The decode step's expert load, as its program counted it: summed
        into ``stats()["moe"]``, and the step's own on its span."""
        held, touched, layers = (int(c) for c in counts)
        moe = self._moe
        moe["tokens"] += len(self.running)
        moe["assignments_held"] += held
        moe["experts_touched"] += touched
        moe["layer_steps"] += layers
        if self._step_span is not None:
            self._step_span.set(moe_tokens=len(self.running),
                                moe_assignments_held=held,
                                moe_experts_touched=touched)

    def _deliver_pulled(self, toks, now: float, step_s: float) -> None:
        if self.journal is not None:
            # ONE batched record per engine step covering every active
            # slot (never one record per token) — the same batching
            # discipline as the tracer's decode_step event
            tokmap = {r.journal_id: int(toks[s])
                      for s, r in self.running.items()
                      if r.journal_id is not None}
            if tokmap:
                self.journal.record_tokens(self.name, self._step_counter,
                                           tokmap)
        self.metrics.on_decode_step(len(self.running), step_s)
        tr = self.tracer
        if tr.enabled:
            # ONE batched event per engine step, never one per token
            tr.on_decode_step(self.name, self._step_counter,
                              list(self.running), step_s, t=now)
        for slot, req in list(self.running.items()):
            self._advance(req)                   # token written this step
            if not self._emit_token(req, int(toks[slot]), now):
                continue
            if req.done:                 # cancelled from inside its cb
                continue
            if self._done_after_emit(req):
                self._retire(req)

    def _prepare_spec_paged(self) -> None:
        """Host-side block maintenance before a speculative round: each
        running slot must exclusively own the blocks covering its whole
        verify window ``[len, len+k]`` (the fixed-shape verify writes
        all k+1 positions regardless of acceptance) — fresh blocks
        appended, shared covering blocks copied-on-extend, exactly the
        per-position ``ensure_capacity`` contract the plain decode path
        uses, applied across the window.  Over-the-end positions of a
        near-capacity slot are excluded (the verify write masks them to
        scratch).  A slot the pool cannot serve fails its request; the
        engine and the rest of the batch continue."""
        k = self.spec.k
        for slot, req in list(self.running.items()):
            ok = True
            try:
                last = min(req._seq_len + k, self.max_seq - 1)
                for pos in range(req._seq_len, last + 1):
                    if not self.cache.ensure_capacity(slot, pos):
                        ok = False
                        break
            except Exception as e:       # noqa: BLE001 — accounting bug
                self._mark_block_corruption(
                    f"ensure_capacity({slot}) failed: "
                    f"{type(e).__name__}: {e}")
                ok = False
            if not ok:
                self.tracer.on_block_pressure(req, self.name,
                                              kind="pool_exhausted",
                                              position=req._seq_len)
                self._retire(req, "failed",
                             error="KV block pool exhausted: no block "
                                   "free for the verify window at "
                                   f"position {req._seq_len} (even "
                                   "after prefix-cache eviction)")

    # tpulint: hot-path
    def _spec_round_body(self):
        """Dispatch one speculative ROUND: k draft-decode steps (the
        proposals chain through the draft sampler's device token lane)
        and one bucketed ``[slots, k+1]`` verify step with in-graph
        acceptance.  Device handles only — no d2h coercion belongs here
        (tpulint TPL106; the sanitizer window covers this dispatch, so
        the measured per-round host transfers stay 0.0).  Returns
        ``(round_tensor, t0)`` or None (nothing ran / round failed)."""
        spec = self.spec
        with _spans.span("engine.prepare_decode"):
            self._prepare_spec_paged()
            if not self.running:
                return None
            active, cap = self._spec_masks()
        san = self.sanitizer
        try:
            with (nullcontext() if san is None else san.compiled_guard()), \
                    _spans.span("engine.decode") as sp:
                act_t = to_tensor(active)
                for j in range(spec.k):
                    self._step_call("serving.spec_draft",
                                    self._draft_decode_fn, act_t,
                                    to_tensor(np.int32(j)), span=sp)
                out = self._step_call("serving.spec_verify",
                                      self._verify_fn, act_t,
                                      to_tensor(cap), span=sp)
        except Exception as e:           # noqa: BLE001 — isolated upstream
            if san is not None and "device-to-host transfer" in str(e):
                san.guard_violations += 1
            msg = (f"speculative round failed after "
                   f"{self.max_step_retries} "
                   f"retr{'y' if self.max_step_retries == 1 else 'ies'}: "
                   f"{type(e).__name__}: {e}")
            for req in list(self.running.values()):
                self._retire(req, "failed", error=msg, kind="replica")
            return None
        if san is not None:
            san.note_step()             # one round == one counted step
        return out, sp.t0

    def _spec_masks(self):
        """``(active, cap)`` of a speculative round, host ints only."""
        spec = self.spec
        active = np.zeros((self.num_slots,), dtype=np.int32)
        cap = np.ones((self.num_slots,), dtype=np.int32)
        for slot, req in self.running.items():
            active[slot] = 1
            # per-slot emission cap: token budget and cache capacity,
            # host ints only — the in-graph acceptance clamps to it
            # (truncating the emission stream is distribution-safe:
            # every emitted position is marginally the target law).
            # Both terms are >= 1 for any request still running —
            # _done_after_emit retires at the budget/capacity boundary
            # before the next round — so the max(1, ...) is a floor for
            # the in-graph clip's domain, never a behavior change.
            cap[slot] = max(1, min(spec.k + 1,
                                   req.max_new_tokens
                                   - len(req.output_ids),
                                   self.max_seq - req._seq_len))
        return active, cap

    def _deliver_spec(self, out, t0: float) -> None:
        """Post-dispatch host half of a speculative round: pull the ONE
        ``[slots, k+2]`` int32 round result (per-slot emitted count +
        emission stream — the same shape-class pull as non-speculative
        stream delivery, outside the sanitizer window and the hot-path
        dispatch), then do the host bookkeeping the in-graph acceptance
        cannot: paged block-table truncation past the accepted length,
        journal/metrics/tracer records (one batched record per ROUND —
        the decode_step discipline), stream callbacks, and retirement
        checks."""
        with _spans.span("engine.pull") as pull:
            arr = out.numpy()            # [slots, k+2] int32
        with _spans.span("engine.deliver") as sp:
            ran = len(self.running)
            self._deliver_round(arr, pull.t1, pull.t1 - t0)
            sp.attrs["retired"] = ran - len(self.running)

    def _deliver_round(self, arr, now: float, step_s: float) -> None:
        spec = self.spec
        running = list(self.running.items())
        delivered: Dict[int, List[int]] = {}
        accepted_total = 0
        for slot, req in running:
            m = int(arr[slot, 0])
            accepted_total += max(0, m - 1)
            toks = [int(t) for t in arr[slot, 1:1 + m]]
            if req.eos_token_id is not None and req.eos_token_id in toks:
                # the round ran past the stop token; everything after
                # it is never delivered (matching the non-speculative
                # loop, which would have stopped there)
                toks = toks[:toks.index(req.eos_token_id) + 1]
            delivered[slot] = toks
        if self.journal is not None:
            # ONE batched record per ROUND, each jid carrying its whole
            # delivered burst (journal BEFORE the user-visible emits:
            # at-least-once delivery across a crash, unchanged)
            tokmap = {r.journal_id: delivered[s]
                      for s, r in running
                      if r.journal_id is not None and delivered[s]}
            if tokmap:
                self.journal.record_tokens(self.name, self._step_counter,
                                           tokmap)
        self.metrics.on_spec_round(
            step_s, draft_steps=spec.k,
            proposed=spec.k * len(running), accepted=accepted_total,
            delivered=[len(delivered[s]) for s, _ in running])
        tr = self.tracer
        if tr.enabled:
            # ONE batched event per ROUND, never one per token — the
            # decode_step discipline with the round's (proposed,
            # accepted) pair riding along
            tr.on_verify_step(self.name, self._step_counter,
                              [s for s, _ in running], step_s,
                              proposed=spec.k * len(running),
                              accepted=accepted_total, t=now)
        for slot, req in running:
            m = int(arr[slot, 0])
            self._advance(req, m)        # the in-graph advance, mirrored
            # rollback bookkeeping: drop table blocks past the
            # accepted length (no copy — refcounts + table writes)
            try:
                self.cache.truncate_blocks(slot, req._seq_len)
            except Exception as e:       # noqa: BLE001 — accounting bug
                self._mark_block_corruption(
                    f"truncate_blocks({slot}) failed: "
                    f"{type(e).__name__}: {e}")
            finished = False
            for tok in delivered[slot]:
                if not self._emit_token(req, tok, now):
                    finished = True      # callback failure retired it
                    break
                if req.done:             # cancelled from inside its cb
                    finished = True
                    break
                if len(req.output_ids) >= req.max_new_tokens or \
                        (req.eos_token_id is not None
                         and req.output_ids[-1] == req.eos_token_id):
                    self._retire(req)
                    finished = True
                    break
            if not finished and not req.done \
                    and req._seq_len + 1 > self.max_seq:
                # cache capacity: checked once per round (the cap
                # already bounded the burst to fit)
                self._retire(req)

    def step(self) -> bool:
        """One scheduler tick: reap cancellations/deadlines, admit queued
        requests into free slots, then run one decode step for all running
        slots (one speculative ROUND when speculation is on).  Returns
        True while there is in-flight or queued work.
        Raises ``EngineStopped`` once the watchdog has marked the engine
        unhealthy."""
        if self.state == "unhealthy":
            raise EngineStopped(
                f"engine {self.name!r} is unhealthy: "
                f"{self._unhealthy_reason}")
        if self.shard is not None and self.fault_plan is not None \
                and self.fault_plan.armed:
            # simulated device loss (serving.shard_fail@N): the engine
            # loses one device of its mesh and goes sticky-unhealthy —
            # the fleet's supervision ejects it and rebuilds the group
            # DEGRADED at a smaller viable mp on the survivors
            from ..distributed.fault_tolerance.injection import \
                InjectedFault
            try:
                self.fault_plan.check("serving.shard_fail")
            except InjectedFault as e:
                self._mark_shard_lost(e)
                raise EngineStopped(
                    f"engine {self.name!r} is unhealthy: "
                    f"{self._unhealthy_reason}") from e
        if self._prefill_fn is None:
            self._build_steps()
        with _spans.span("engine.step", step=self._step_counter,
                         kv_tokens=self._kv_tokens) as sp:
            self._admitted_step = 0
            self._step_span = sp
            self._schedule(sp.t0)
            self._step_span = None
            self._step_counter += 1
            sp.set(admitted=self._admitted_step,
                   running=len(self.running), queued=len(self.queue),
                   free_blocks=self.cache.allocator.free_blocks)
        # the step's one record: the closed span's stamps and attributes
        # are the health clock and the always-on flight ring's summary
        # (the post-mortem tail), with no clock read of their own
        self._last_step_t = sp.t1
        self.flight.record_span(sp)
        return bool(self.running or self.queue)

    def _schedule(self, now: float) -> None:
        """The body of one tick: reap, admit, decode."""
        with _spans.span("engine.reap"):
            self._reap(now)
        while self.queue:
            now_a = time.perf_counter()
            i = self._best_queued_index(now_a)
            req = self.queue[i]
            if req.done:                 # cancelled/expired while queued
                del self.queue[i]
                continue
            if not self.free_slots:
                # slot-table pressure: the entitled queued request (not
                # necessarily the effective head — aging grants queue
                # position, never eviction rights) may evict the
                # lowest-priority running victim; otherwise the queue
                # waits for a natural retirement
                i, req, victim = self._best_preempting_candidate(now_a)
                if victim is None:
                    break
                del self.queue[i]
                self._preempt(victim)
            else:
                del self.queue[i]
            req.slot = self.free_slots.pop()
            try:
                deferred = self._admit(req) is False
            except BaseException:
                # _admit isolates request-level failures itself; this is
                # the guarantee that even an engine-level bug (or
                # KeyboardInterrupt mid-prefill) cannot leak the slot
                if not req.done:
                    self._retire(req, "failed",
                                 error="admission aborted by engine error")
                raise
            if deferred:
                # the pool has no blocks for this prompt
                # right now — hand the slot back.  A higher-priority
                # admission may evict a lower-priority victim (freeing
                # its blocks) and retry immediately; otherwise requeue
                # at the head and retry once running work retires.  With
                # nothing running, no block can ever free (eviction was
                # already attempted inside alloc), so fail instead of
                # spinning forever.
                self.free_slots.append(req.slot)
                req.slot = None
                req._defers += 1
                self.tracer.on_block_pressure(req, self.name,
                                              defers=req._defers)
                victim = self._pick_victim(req)
                if victim is not None:
                    self._preempt(victim)
                    self.queue.appendleft(req)
                    continue
                if self.running:
                    self.queue.appendleft(req)
                else:
                    self._retire(req, "failed",
                                 error="KV block pool exhausted: prompt "
                                       "needs more free blocks than the "
                                       "pool can supply")
                break
        self.metrics.on_slots(len(self.running))
        if self.running:
            self._decode()

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive ``step()`` until idle (or ``max_steps``)."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                break

    def generate(self, prompts: Sequence[Sequence[int]], *,
                 max_new_tokens: int = 16, **request_kwargs
                 ) -> List[List[int]]:
        """Synchronous convenience: serve a batch of prompts through the
        continuous-batching loop; returns generated ids per prompt."""
        reqs = [self.add_request(p, max_new_tokens=max_new_tokens,
                                 **request_kwargs) for p in prompts]
        self.run()
        return [r.output_ids for r in reqs]

    # -- lifecycle ---------------------------------------------------------

    def drain(self, max_steps: Optional[int] = None) -> dict:
        """Stop admitting new requests, finish all queued and in-flight
        work, and return the final stats snapshot.  The engine ends in the
        ``stopped`` state (``add_request`` raises ``EngineStopped``)."""
        if self.state == "active":
            self.state = "draining"
        n = 0
        while (self.running or self.queue) and self.state == "draining":
            try:
                self.step()
            except EngineStopped:
                break                    # wedged mid-drain: sticky unhealthy
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        if self.state == "draining" and not (self.running or self.queue):
            self.state = "stopped"
            self._stop_watchdog()
        return self.stats()

    def shutdown(self, timeout_s: Optional[float] = None) -> dict:
        """Drain with a wall-clock budget, then cancel whatever work is
        still unfinished and stop the engine.  ``timeout_s=None`` waits
        for all work (equivalent to ``drain()`` + final cleanup)."""
        if self.state == "active":
            self.state = "draining"
        deadline = None if timeout_s is None \
            else time.perf_counter() + float(timeout_s)
        while (self.running or self.queue) and self.state == "draining":
            if deadline is not None and time.perf_counter() >= deadline:
                break
            try:
                self.step()
            except EngineStopped:
                break                    # wedged mid-drain: cancel the rest
        for req in list(self.queue) + list(self.running.values()):
            # lifecycle cancellation implicates the ENGINE, not the
            # request — a fleet supervisor may replay these elsewhere
            self._retire(req, "cancelled", error="engine shutdown",
                         kind="replica")
        self.queue.clear()
        self.metrics.queue_depth = 0
        if self.state != "unhealthy":
            self.state = "stopped"
        self._stop_watchdog()
        return self.stats()

    # -- fleet-supervisor hooks --------------------------------------------

    def export_requests(self) -> List[Request]:
        """Strip every non-terminal request off this engine for
        re-dispatch elsewhere — the ejection hook of the fleet
        supervisor (``serving.router.Fleet``).

        Queued AND in-flight requests are returned in scheduling order
        (queue first, then running slots) after being retired here as
        ``cancelled`` with ``error_kind="replica"`` — the single retire
        path reclaims their slots (and paged blocks) even on an engine
        mid-corruption, so the exported handles carry no live engine
        state.  The caller replays each from its original prompt; this
        engine is then safe to shut down or discard."""
        out = [r for r in self.queue if not r.done]
        out.extend(r for r in self.running.values() if not r.done)
        self.queue.clear()
        for req in out:
            self._retire(req, "cancelled",
                         error=f"exported from engine {self.name!r} "
                               "on replica ejection",
                         kind="replica")
        self.metrics.queue_depth = 0
        return out

    def prefix_probe(self, prompt_ids: Sequence[int],
                     adapter: Optional[str] = None) -> int:
        """Longest prompt prefix (in tokens) this engine's prefix cache
        already holds — side-effect-free (no LRU refresh, no counters,
        no refs).  0 for a disabled/failing cache; the fleet router's affinity signal.  ``adapter`` probes
        under that tenant's salt (cached KV is tenant-keyed; a base
        probe can never see adapter blocks and vice versa)."""
        if self.prefix_cache is None:
            return 0
        salt = b""
        if adapter is not None and self.adapter_pool is not None:
            try:
                salt = self.adapter_pool.salt(adapter)
            except KeyError:
                return 0                 # unloaded → no cached KV here
        try:
            return self.prefix_cache.probe(prompt_ids, salt=salt)
        except Exception:                # noqa: BLE001 — advisory only
            return 0

    # -- durability: crash recovery & weight hot-swap ----------------------

    def recover(self, journal=None, *, cross_mesh: bool = True) -> dict:
        """Crash-consistent recovery: rehydrate every non-terminal
        journaled request (admission recorded, no final end) and
        re-enqueue it as a replay-from-prompt under the stream-restart
        contract — ``recovered`` flag set, stream restarting at token
        0, the slot's device key lane re-seeded from the JOURNALED
        effective seed so greedy and seeded outputs are bitwise
        identical to an uninterrupted run.  Pre-crash terminal
        outcomes are banked into the metrics so the counters stay
        monotone across the restart.

        **Cross-mesh replay** (``cross_mesh=True``, the default): a
        request journaled at a DIFFERENT mesh shape replays here anyway
        — sharded decoding is bitwise identical across viable ``mp``
        (the tier-1 parity suite proves it), so a degraded rebuild at a
        smaller mesh serves the same tokens the original shape
        promised.  Each shape change is journaled as a ``mesh_reshard``
        record (old → new shape, per-request disposition) so
        ``audit()`` spans the degradation exactly-once.
        ``cross_mesh=False`` restores the strict contract: a
        shape-mismatched admission fails finally instead of replaying.

        Call on a fresh engine AFTER ``warmup()`` and before any
        traffic.  ``journal`` defaults to the engine's own; passing one
        here also attaches it.  Returns ``{"replayed", "requests",
        "invalid", "cross_mesh", "outcomes"}``."""
        journal = journal if journal is not None else self.journal
        if journal is None:
            raise ValueError("recover() needs a RequestJournal (pass "
                             "journal= here or to the Engine)")
        if self.running or self.queue:
            raise RuntimeError("recover() must run before serving "
                               "traffic (the journal's replay order is "
                               "the recovered queue order)")
        if self.journal is not None and journal is not self.journal:
            raise ValueError(
                "recover(journal=...) does not match the journal this "
                "engine records into — replaying one journal while "
                "recording into another leaves the replayed journal's "
                "pending set non-converging")
        self.journal = journal
        outcomes = journal.outcomes()
        self.metrics.bank_outcomes(outcomes)
        replayed, invalid = [], []
        # cross-shape dispositions, grouped by the journaled old shape:
        # one mesh_reshard record per shape spans the degradation
        cross: "OrderedDict[Optional[str], OrderedDict[str, str]]" = \
            OrderedDict()
        saved_max_queue, self.max_queue = self.max_queue, None
        try:
            for jid, rec in journal.pending().items():
                want = rec.get("mesh_shape")
                shape_changed = want != self.mesh_shape
                if shape_changed and not cross_mesh:
                    # strict mode: a request admitted sharded carries
                    # its mesh-shape key, and a recovering engine of a
                    # different shape fails that replay finally rather
                    # than serve it on a topology the journal never
                    # promised
                    journal.record_end(
                        jid, "failed", final=True,
                        error=f"recovery replay rejected: journaled "
                              f"mesh shape {want!r} != this engine's "
                              f"{self.mesh_shape!r}",
                        engine=self.name)
                    invalid.append(jid)
                    continue
                s = journal.replay_sampling(rec)
                journal.begin_attempt(jid, recovered=True,
                                      origin_wall=rec.get("wall"))
                try:
                    self._validate_replay_tenancy(rec, s)
                    r = self.add_request(
                        rec["prompt_ids"],
                        max_new_tokens=rec["max_new_tokens"],
                        sampling=SamplingParams(**s),
                        eos_token_id=rec["eos_token_id"],
                        deadline_s=rec["deadline_s"],
                        priority=rec["priority"])
                except ValueError as e:
                    # a replay this engine cannot validate (e.g. the
                    # restart shrank max_seq): fail THAT request with a
                    # final end so the journal converges instead of
                    # wedging every future recover() on the same jid —
                    # and keep replaying the rest
                    journal.record_end(jid, "failed", final=True,
                                       error=f"recovery replay "
                                             f"rejected: {e}",
                                       engine=self.name)
                    invalid.append(getattr(e, "request", None) or jid)
                    if shape_changed:
                        cross.setdefault(want, OrderedDict())[jid] = \
                            "failed"
                    continue
                finally:
                    journal.end_attempt()
                replayed.append(r)
                if shape_changed:
                    cross.setdefault(want, OrderedDict())[jid] = \
                        "replayed"
        finally:
            self.max_queue = saved_max_queue
        for old_shape, requests in cross.items():
            journal.record_mesh_reshard(
                self.name, old_shape, self.mesh_shape, requests)
        return {"replayed": len(replayed), "requests": replayed,
                "invalid": invalid,
                "cross_mesh": sum(len(v) for v in cross.values()),
                "outcomes": outcomes}

    def _validate_replay_tenancy(self, rec: dict, s: dict) -> None:
        """Bitwise-replay gate for a journaled tenant request: the
        adapter must still be loaded AT THE JOURNALED VERSION (replaying
        onto other weights would silently serve different tokens than
        the crash-interrupted run promised) and the grammar must exist.
        Raises ValueError — the caller's invalid-replay isolation path
        fails THIS request finally and keeps replaying the rest."""
        a = s.get("adapter")
        if a is not None:
            err_ctx = None
            if self.adapter_pool is None:
                err_ctx = {"adapter": a, "version": rec.get(
                    "adapter_version")}
                msg = (f"journaled adapter {a!r} but this engine has "
                       "no adapter pool")
            else:
                try:
                    _, v = self.adapter_pool.resolve(a)
                except KeyError:
                    v = None
                want = rec.get("adapter_version")
                if v is None:
                    err_ctx = {"adapter": a, "version": want}
                    msg = (f"journaled adapter {a!r} (v{want}) is not "
                           "loaded on the recovering engine")
                elif want is not None and v != want:
                    err_ctx = {"adapter": a, "version": want}
                    msg = (f"journaled adapter {a!r} v{want} != loaded "
                           f"v{v}: bitwise replay impossible")
            if err_ctx is not None:
                e = ValueError(msg)
                e.error_ctx = err_ctx
                raise e
        g = s.get("grammar")
        if g is not None:
            if self.grammar_table is None:
                raise ValueError(f"journaled grammar {g!r} but this "
                                 "engine has no grammar table")
            try:
                self.grammar_table.spec_of(g)
            except KeyError as e:
                raise ValueError(e.args[0]) from None

    def update_weights(self, state_or_path, *,
                       version: Optional[int] = None) -> int:
        """Hot-swap the model weights IN PLACE on an idle engine.

        The write goes *through* the existing parameter buffers
        (``set_state_dict`` ``_set_data`` write-through), so every
        warmed executable and its lifted state stay valid — zero new
        compile keys, pinned by the shape manifest.  The prefix-cache
        **version epoch** is bumped so no later request can prefix-hit
        KV blocks prefilled under the old weights, and
        ``model_version`` advances so every admission records which
        weights served it.

        The engine must be idle (no queued or running work): an
        in-flight request's KV was computed under the old weights and
        decoding it under new ones would serve a torn hybrid.  The
        fleet's rolling ``update_weights`` guarantees that by draining
        one replica at a time.  Accepts a state dict, an ``.npz`` path,
        or a ``distributed.checkpoint.save_state_dict`` directory.
        Returns the new version."""
        if self.running or self.queue:
            raise RuntimeError(
                f"engine {self.name!r} has in-flight work "
                f"({len(self.running)} running, {len(self.queue)} "
                "queued): drain before update_weights — decoding KV "
                "prefilled under old weights with new weights would "
                "serve a torn response")
        sd = _resolve_weights(state_or_path)
        _write_state_dict(self.model, sd)
        if self.shard is not None:
            # set_state_dict's _set_data write-through landed host
            # arrays in the parameter buffers — re-place them under
            # their TP specs so the warmed executables keep their
            # shardings (same specs as at construction: no new keys)
            self.shard.place_model(self.model)
        return self._mark_weights_swapped(version)

    def _mark_weights_swapped(self, version: Optional[int] = None) -> int:
        """The per-engine half of a weight swap — prefix-epoch bump,
        version tag, metrics/tracer/journal — split out so a fleet
        whose replicas SHARE one parameter set (the stop-the-world
        fallback) can write the state dict once and still give every
        engine its own epoch/version bookkeeping."""
        if self.prefix_cache is not None:
            self.prefix_cache.bump_epoch()
        self.model_version = (int(version) if version is not None
                              else self.model_version + 1)
        self.metrics.on_weight_swap(self.model_version)
        self.tracer.on_weight_swap(self.name, self.model_version)
        if self.journal is not None:
            self.journal.record_weight_swap(self.name, self.model_version)
        return self.model_version

    # -- multi-LoRA adapter lifecycle --------------------------------------

    def _fail_adapter_inflight(self, name: str, why: str) -> int:
        """Fail every queued and running request pinned to adapter
        ``name`` with machine-readable ``error_ctx`` — the unload /
        hot-swap contract: a lane about to be zeroed or overwritten in
        place must never keep serving a request that pinned the old
        version (that would be a torn hybrid).  Returns how many
        requests were failed."""
        v = self.adapter_pool.last_version(name)
        failed = 0
        hit = [q for q in list(self.queue)
               if q.sampling.adapter == name]
        for q in hit:
            try:
                self.queue.remove(q)
            except ValueError:
                continue                 # claimed by a concurrent path
            q.error_ctx = {"adapter": name, "version": v}
            self._retire(q, "failed",
                         error=f"adapter {name!r} {why} while queued "
                               f"(was v{v})")
            failed += 1
        for r in [r for r in list(self.running.values())
                  if r.sampling.adapter == name]:
            r.error_ctx = {"adapter": name, "version": v}
            self._retire(r, "failed",
                         error=f"adapter {name!r} {why} mid-flight "
                               f"(was v{v})")
            failed += 1
        self.metrics.queue_depth = len(self.queue)
        return failed

    def load_adapter(self, name: str, weights, *,
                     scale: float = 1.0) -> int:
        """Load (or hot-swap) LoRA adapter ``name`` into a pool lane.
        A hot swap (load over an already-loaded name) first FAILS that
        adapter's in-flight requests — the lane is overwritten in place,
        and a request that pinned the old version must not decode under
        a torn mix of both.  Bumps the name's version (retiring its old
        prefix-cache salt) and returns it."""
        if self.adapter_pool is None:
            raise RuntimeError(
                f"engine {self.name!r} has no adapter pool "
                "(construct with Engine(adapters=...))")
        if name in self.adapter_pool.loaded:
            self._fail_adapter_inflight(name, "hot-swapped")
        _lane, version = self.adapter_pool.load(name, weights,
                                                scale=scale)
        if self.shard is not None:
            # the _set_data writes landed host arrays — re-pin the lane
            # tensors under their TP specs (same specs: no new keys)
            self.shard.place_adapters(self.adapter_pool)
        self.metrics.on_adapter_load(name, version)
        self.tracer.on_adapter_load(self.name, name, version)
        return version

    def unload_adapter(self, name: str) -> int:
        """Unload adapter ``name``: fail its in-flight requests (with
        ``error_ctx = {"adapter", "version"}``), zero and free its lane.
        The name's version counter survives for a later reload, so the
        unloaded version's prefix-cache salt can never be minted again.
        Returns the unloaded version."""
        if self.adapter_pool is None:
            raise RuntimeError(
                f"engine {self.name!r} has no adapter pool "
                "(construct with Engine(adapters=...))")
        self.adapter_pool.resolve(name)  # KeyError if not loaded
        self._fail_adapter_inflight(name, "unloaded")
        version = self.adapter_pool.unload(name)
        if self.shard is not None:
            self.shard.place_adapters(self.adapter_pool)
        self.metrics.on_adapter_unload(name, version)
        self.tracer.on_adapter_unload(self.name, name, version)
        return version

    def _stop_watchdog(self) -> None:
        """Join and drop the watchdog thread so a drained/stopped engine
        holds no thread alive (its bound-method callback would otherwise
        pin the engine — model and KV cache included — forever)."""
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None

    def _paging_snapshot(self) -> dict:
        """The paged-KV observability payload (``stats()["paging"]`` and
        ``profiler.serving_paging()``): block-pool occupancy, eviction and
        copy-on-extend counters, and the prefix-cache hit counters."""
        al = self.cache.allocator.stats()
        return {
            "kv_layout": "paged",
            "kernel": self.kernel,
            "block_size": self.block_size,
            "max_blocks_per_slot": self.cache.max_blocks_per_slot,
            "blocks": al,
            "blocks_in_use": al["used"] + al["cached"],
            "copy_on_extends": self.cache.copy_on_extends,
            "prefix": (self.prefix_cache.stats()
                       if self.prefix_cache is not None else None),
            # a cache stated by layer: a row a group ("blocks" above is the
            # first group's, the one that keeps every token)
            **({"groups": self.cache.group_stats()}
               if self.cache_spec.layer_groups else {}),
        }

    def health(self) -> dict:
        """Liveness snapshot: engine state, last-step age, consecutive
        compiled-step failures, and capacity gauges — the probe a load
        balancer or the profiler surface polls.  It also audits the block allocator's invariants (free + used + cached ==
        total − reserved, no negative refcounts, no slot holding a freed
        block) and flips the engine ``unhealthy`` on any violation
        instead of letting the pool corrupt silently."""
        violations = self.cache.check_invariants()
        if violations:
            # health() may be polled from a monitor thread while the
            # scheduler is mid-way through a multi-op accounting
            # change (block popped, refcount not yet set): confirm on
            # a re-read before declaring the pool corrupt — a
            # transient snapshot clears, real corruption persists
            violations = self.cache.check_invariants()
        if violations:
            self._mark_block_corruption("; ".join(violations))
        al = self.cache.allocator.stats()
        now = time.perf_counter()
        return {
            "kv_blocks": {k: al[k] for k in
                          ("total", "reserved", "free", "used", "cached")},
            "kv_block_invariants": violations or "ok",
            "state": self.state,
            "reason": self._unhealthy_reason,
            "steps": self._step_counter,
            "last_step_age_s": None if self._last_step_t is None
            else round(now - self._last_step_t, 3),
            "consecutive_step_failures": self._consecutive_failures,
            "queue_depth": len(self.queue),
            "slots_free": len(self.free_slots),
            "slots_total": self.num_slots,
            # armed = hang detection is actually protecting future steps:
            # configured, engine still stepping, monitor thread not yet
            # fired/stopped (it is started lazily at the first step)
            "watchdog_armed": bool(
                self.step_timeout_s is not None
                and self.state in ("active", "draining")
                and (self._watchdog is None or self._watchdog.alive)),
        }

    def stats(self) -> dict:
        """``/stats``-style snapshot (also exported through
        ``paddle_tpu.profiler.serving_stats()``)."""
        self.metrics._slots_busy = len(self.running)
        self.metrics.queue_depth = len(self.queue)
        snap = self.metrics.snapshot()
        if self.adapter_pool is not None or self.grammar_table is not None:
            snap["tenancy"] = {
                "adapters": (self.adapter_pool.loaded
                             if self.adapter_pool is not None else {}),
                "adapter_lanes": (self.adapter_pool.max_adapters
                                  if self.adapter_pool is not None
                                  else 0),
                "grammars": (list(self.grammar_table.names)
                             if self.grammar_table is not None else []),
            }
        if self._moe["layer_steps"]:
            snap["moe"] = dict(self._moe)
        if self.cache_spec.kind == "indexed":
            snap["sparse"] = dict(self._sparse)
        if self.cache_spec.kind == "latent":
            snap["latent"] = dict(self._latent)
        if self.cache_spec.kind == "windowed":
            snap["eva"] = dict(
                self._eva,
                exact_blocks_in_use=self.cache.blocks_in_use(),
                summary_blocks_in_use=self.cache.summary_blocks_in_use(),
                exact_blocks_released=self.cache.exact_blocks_released)
        if self.cache_spec.layer_groups:
            snap["swa"] = dict(
                self._swa, groups=self.cache.group_stats(),
                deferred_by_group=list(self.cache.deferred_by),
                hits_shortened=(self.prefix_cache.hits_shortened
                                if self.prefix_cache is not None else 0))
            if self.cache.states:
                pc = self.prefix_cache
                snap["state"] = dict(
                    self._state, groups=[p.stats() for p in self.cache.states],
                    hits_shortened=pc.hits_shortened if pc is not None else 0,
                    snapshot_evictions=sum(c.evictions
                                           for c in pc.state_chains)
                    if pc is not None else 0)
        snap["sampler"] = dict(self._sampler_steps)
        snap["admission"] = dict(self._admission)
        if self.shard is not None:
            snap["sharding"] = {"mesh_shape": self.mesh_shape,
                                "model_parallel": self.shard.mp}
        if self.journal is not None:
            snap["durability"]["journal"] = self.journal.stats()
        if self.sanitizer is not None:
            snap["sanitizer"] = self.sanitizer.report()
        if self.tracer.enabled:
            snap["tracing"] = self.tracer.snapshot()
        return snap

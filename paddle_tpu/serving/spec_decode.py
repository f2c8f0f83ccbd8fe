"""Speculative decoding: draft-propose, bucketed verify, device accept.

The decode loop's cost is one target-model forward per emitted token.
Speculative decoding breaks that coupling (ROADMAP item 4(b)): a small
**draft model** proposes ``k`` tokens autoregressively (k cheap forwards),
then the target model scores the last emitted token plus all k proposals
in ONE fixed-shape ``[slots, k+1]`` **verify step** — a k+1-wide bucket
through the same ``CacheContext`` machinery as prefill/decode — and
standard rejection-sampling acceptance keeps the longest valid draft
prefix plus one bonus/resample token.  Per round each slot emits between
1 and k+1 tokens for one target-window forward, so a well-matched draft
cuts target forwards per token by up to (k+1)×.

Fit with the engine's discipline (docs/SERVING.md "Speculative
decoding"):

- **Fixed shapes, zero steady-state recompiles.**  One draft-prefill
  program per bucket, ONE draft-decode program (the proposal column
  index ``j`` is a traced scalar argument), ONE verify program.  Slot
  index, lengths, active mask, caps, and proposals are all argument or
  state *values* — the compiled key set stays closed
  (``tools/shape_manifest.json`` ``speculative`` section).
- **Zero host transfers per round.**  Proposals chain through the draft
  sampler's device token lane, the verify step consumes them from the
  ``proposals`` state lane, and acceptance runs in-graph
  (:meth:`DeviceSampler.accept_speculative`).  The host pulls ONE small
  ``[slots, k+2]`` int32 array per round for stream delivery —  the
  same shape-class pull as non-speculative decode's token array, and
  outside the sanitizer window.
- **Greedy is bitwise.**  A greedy slot's every emitted token is the
  target argmax at its position, so speculative greedy output is
  bitwise identical to non-speculative decoding; seeded sampling is
  distribution-preserving by the rejection-sampling identity.
- **Rollback is bookkeeping.**  Rejected window positions are rolled
  back by the in-graph length advance (only ``m`` of ``k+1`` writes
  become readable); paged mode additionally truncates the slot's block
  table past the accepted length (refcount moves, no copies).
- **The draft's KV window is recomputed inside the verify step.**  The
  verify program runs the draft model over the same ``[slots, k+1]``
  window (after rewinding the draft lengths to the round start), which
  (a) supplies the exact proposal law for the acceptance ratio without
  stashing ``[slots, k, V]`` probabilities, and (b) writes the draft KV
  for ALL window positions — so even a fully-accepted round leaves both
  caches in lockstep (``draft length == target length``) with one
  pending token, and no per-slot catch-up state exists anywhere.  The
  draft runs twice per window; the premise of speculation is that the
  draft is small enough for that to be noise against the target.

Durability: draft KV is deliberately NOT journaled/durable — crash
recovery and preemption both replay from the prompt, which re-prefills
the draft cache as a side effect of re-admission (the PR 6/8/13
stream-restart contract covers a speculating request unchanged).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .kv_cache import CacheContext, KVCache
from .sampling import DeviceSampler

__all__ = ["SpecConfig", "SpecState"]

#: Mixed into the request's effective seed to derive the draft model's
#: key-lane seed: the draft must draw an independent stream (its
#: proposals are priced by the acceptance ratio, not replayed by the
#: target), but a deterministic one — preempt-resume and journal
#: recovery re-seed both lanes from the same journaled effective seed.
DRAFT_SEED_SALT = 0x5DC0DE


@dataclass
class SpecConfig:
    """Opt-in speculative decoding for :class:`~.engine.Engine`.

    Args:
        draft_model: the proposal model — anything
            ``Engine.resolve_model`` accepts (a model Layer, a
            ``GPTConfig``/``LlamaConfig``, or a registry name like
            ``"gpt:tiny"``).  Must share the target's vocabulary and
            cover the engine's ``max_seq`` positions.  May be the
            target model itself (self-speculation — useful as a
            deterministic full-acceptance drill).
        k: draft tokens proposed per round (the verify bucket is
            ``k + 1`` wide).  Each round costs k draft steps + one
            verify step and emits 1..k+1 tokens per slot.
        draft_cache_dtype: draft KV cache dtype (default: the draft
            model's parameter dtype, like the engine's own cache).
    """

    draft_model: Any
    k: int = 4
    draft_cache_dtype: Optional[str] = None

    def __post_init__(self):
        if int(self.k) < 1:
            raise ValueError(f"SpecConfig.k must be >= 1, got {self.k}")


class SpecState:
    """Per-engine speculative-decoding state: the draft model, its own
    dense per-slot KV cache (sharing the engine's slot table — slot ``i``
    of the draft cache mirrors slot ``i`` of the target's), the draft
    :class:`DeviceSampler` (proposal params/keys/token lanes), and the
    ``[slots, k]`` proposals lane the verify step consumes.

    The draft cache is a dense :class:`KVCache`, not a second pool — it
    is small by construction (draft model × max_seq) and holds no
    shareable prefixes worth paging; its ``max_seq`` carries ``k``
    positions of headroom so a near-capacity round's draft steps never
    clamp a write onto a live position.
    """

    def __init__(self, engine, config: SpecConfig):
        from .engine import Engine

        self.config = config
        self.k = int(config.k)
        model = Engine.resolve_model(config.draft_model)
        dcfg = getattr(model, "config", None)
        if dcfg is None:
            raise TypeError("SpecConfig.draft_model needs a model "
                            "carrying a .config")
        if dcfg.vocab_size != engine.config.vocab_size:
            raise ValueError(
                f"draft vocab_size {dcfg.vocab_size} != target "
                f"{engine.config.vocab_size}: speculative acceptance "
                "compares distributions over one shared vocabulary")
        max_pos = getattr(dcfg, "max_position_embeddings", None)
        if max_pos is not None and max_pos < engine.max_seq:
            raise ValueError(
                f"draft max_position_embeddings {max_pos} < engine "
                f"max_seq {engine.max_seq}: the draft must cover every "
                "position it verifies")
        self.model = model
        self.model.eval()
        dtype = config.draft_cache_dtype
        if dtype is None:
            params = model.parameters()
            dtype = params[0].dtype if params else "float32"
        kv_heads = getattr(dcfg, "n_kv_heads", None) \
            or dcfg.num_attention_heads
        self.cache = KVCache(
            num_slots=engine.num_slots,
            num_layers=dcfg.num_hidden_layers,
            max_seq=engine.max_seq + self.k,
            num_kv_heads=kv_heads, head_dim=dcfg.head_dim, dtype=dtype)
        # the draft sampler shares the ENGINE's grammar table (one
        # stacked trans/mask pair serves both models), so draft
        # proposals are drawn from the same masked support the verify
        # step prices — see DeviceSampler.accept_speculative
        self.sampler = DeviceSampler(engine.num_slots,
                                     grammar=engine.sampler.grammar)
        self.proposals = Tensor._wrap(
            jnp.zeros((engine.num_slots, self.k), dtype=jnp.int32))
        self.proposals.persistable = True

    # -- host-side slot lifecycle (value-only, never a shape) --------------

    @staticmethod
    def draft_seed(seed: int) -> int:
        return int(seed) ^ DRAFT_SEED_SALT

    def stage_slot(self, slot: int, params, seed: int) -> None:
        """Stage the draft lanes at admission (and preempt-resume /
        recovery re-admission): same sampling params as the target —
        the proposal law the acceptance ratio prices — with a
        salt-derived, deterministic key seed."""
        self.sampler.stage_slot(slot, params, self.draft_seed(seed))

    def release_slot(self, slot: int) -> None:
        """Forget a retired/preempted slot's draft sequence (the KV
        bytes become unreadable; re-admission re-prefills)."""
        self.cache.set_length(slot, 0)

    def reset(self) -> None:
        """Forget everything (warmup scribbles slot 0's draft state)."""
        self.cache.reset()
        self.sampler.reset()
        self.proposals._set_data(
            jnp.zeros(self.proposals.shape, dtype=jnp.int32))

    # -- program bodies (wrapped by Engine._build_steps via to_static) -----

    def make_draft_prefill(self, engine):
        """Draft prompt prefill, one program per bucket: writes the
        prompt's draft KV into the slot and chains the draft token lane
        off the target's pending (prefill-sampled) first token — so the
        first draft step of the first round feeds device-side."""
        spec = self

        def draft_prefill(input_ids, slot, length):
            ctx = CacheContext(spec.cache, "prefill", slot=slot,
                               length=length)
            spec.model(input_ids, cache_ctx=ctx)
            spec.cache.set_length(slot, length)
            s = slot._value().astype(jnp.int32).reshape(())
            tok = jax.lax.dynamic_index_in_dim(
                engine.sampler.tokens._value(), s, 0, keepdims=False)
            spec.sampler.tokens._set_data(
                spec.sampler.tokens._value().at[s].set(tok))
            if spec.sampler.grammar is not None:
                # sync the automaton alongside the token it chains off:
                # the target's prefill advanced past the first sampled
                # token; the draft's first round starts from that state
                gst = jax.lax.dynamic_index_in_dim(
                    engine.sampler.grammar_states._value(), s, 0,
                    keepdims=False)
                spec.sampler.grammar_states._set_data(
                    spec.sampler.grammar_states._value().at[s].set(gst))
            return Tensor._wrap(tok)

        return draft_prefill

    def make_draft_decode(self, engine):
        """ONE draft-decode program for every proposal position: the
        column index ``j`` is a traced scalar, so k sequential calls
        per round share one compiled key.  Each call feeds the draft
        token lane, writes this proposal into ``proposals[:, j]``, and
        chains the lane for the next call."""
        spec = self

        def draft_decode(active, j):
            tokens = Tensor._wrap(spec.sampler.tokens._value()[:, None])
            ctx = CacheContext(spec.cache, "decode", active=active)
            logits = spec.model(tokens, cache_ctx=ctx)
            spec.cache.advance(active)
            prop = spec.sampler.sample_all(
                logits._value()[:, -1, :].astype(jnp.float32),
                active._value())
            jcol = j._value().astype(jnp.int32).reshape(())
            spec.proposals._set_data(jax.lax.dynamic_update_slice(
                spec.proposals._value(), prop[:, None],
                (jnp.int32(0), jcol)))
            return Tensor._wrap(prop)

        return draft_decode

    def make_verify(self, engine):
        """The verify program: one ``[slots, k+1]`` target forward over
        (pending token + proposals), the draft's window recomputed in
        the same program (rewound to the round-start offset — see the
        module docstring for why), in-graph acceptance, and the length
        advance that IS the rollback (only the accepted prefix + bonus
        become readable)."""
        spec = self
        W = self.k + 1

        def verify_step(active, cap):
            draft_toks = spec.proposals._value()
            toks = jnp.concatenate(
                [engine.sampler.tokens._value()[:, None], draft_toks],
                axis=1)                                  # [slots, W]
            t_in = Tensor._wrap(toks)
            tctx = CacheContext(engine.cache, "verify", active=active,
                                width=W)
            pool = engine.adapter_pool
            if pool is not None:
                # target verifies under each slot's adapter lane; the
                # draft below runs un-adapted (acceptance prices the
                # real draft law — see serving.adapters docstring)
                pool.set_rows(pool.adapter_ids._value())
            try:
                tlogits = engine.model(t_in, cache_ctx=tctx)
            finally:
                if pool is not None:
                    pool.clear_rows()
            # rewind the draft to the round-start offset (its k decode
            # steps advanced it) and recompute its window: draft KV for
            # all W positions + the exact proposal law for acceptance
            spec.cache.lengths._set_data(engine.cache.lengths._value())
            dctx = CacheContext(spec.cache, "verify", active=active,
                                width=W)
            dlogits = spec.model(t_in, cache_ctx=dctx)
            emitted, m = engine.sampler.accept_speculative(
                tlogits._value().astype(jnp.float32),
                dlogits._value().astype(jnp.float32),
                draft_toks, cap._value().astype(jnp.int32),
                spec.sampler)
            adv = m * active._value().astype(jnp.int32)
            engine.cache.advance(adv)
            spec.cache.advance(adv)
            out = jnp.concatenate([adv[:, None], emitted], axis=1)
            return Tensor._wrap(out.astype(jnp.int32))

        return verify_step

    def nbytes(self) -> int:
        return self.cache.nbytes()

    def snapshot(self) -> dict:
        """Config half of ``stats()["speculation"]`` (the counters live
        in :class:`~.metrics.ServingMetrics`)."""
        return {
            "k": self.k,
            "draft_layers": self.cache.num_layers,
            "draft_cache_mb": round(self.cache.nbytes() / 2 ** 20, 3),
        }

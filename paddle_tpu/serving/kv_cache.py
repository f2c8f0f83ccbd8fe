"""What a model caches, the handle its layers reach a cache through, and
the speculative draft's dense cache.

There are two cache objects.  The ENGINE's cache is always the paged pool
(:class:`~.paging.PagedKVCache`, reached through
:class:`~.paging.PagedCacheContext`).  :class:`KVCache` here is the dense
per-slot cache ``SpecState`` keeps for the speculative DRAFT model, and
:class:`CacheContext` is the draft's handle and the base class the paged
context extends.

Design of the dense cache: ONE preallocated array per K and V of shape
``[slots, layers, max_seq, kv_heads, head_dim]`` plus a ``[slots]`` int32
length vector.  Every shape a program over it compiles is a function of
(slots, bucket, max_seq) only — never of request content — so XLA
compiles each program once and steady-state serving runs zero recompiles.

State threading: the cache payloads are ordinary eager ``Tensor``s.  Inside
a ``jit.to_static`` trace, reads go through ``Tensor._value`` (lifted to
program inputs) and writes through ``Tensor._set_data`` (lifted to program
outputs and rebound after the call) — exactly how optimizer accumulators
thread through a compiled train step, so the cache needs no explicit
functional plumbing and buffer donation updates it in place.

Write discipline (why stale bytes are never read):
- prefill writes positions ``0..bucket-1`` of a slot (garbage past the real
  prompt length L) and sets ``lengths[slot] = L``;
- decode writes each active slot's token at position ``lengths[slot]`` and
  THEN advances ``lengths`` by the active mask;
- attention only reads positions ``<= lengths[slot]`` (current token
  included).  Every readable position was written by the current request,
  so slot reuse needs no cache zeroing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import dtype as dtype_mod

__all__ = ["KVCache", "CacheContext", "CacheSpec", "CacheGroup",
           "cache_spec_of"]


def _as_i32(x):
    if isinstance(x, Tensor):
        return x._value().astype(jnp.int32)
    return jnp.asarray(x, dtype=jnp.int32)


@dataclass(frozen=True)
class CacheGroup:
    """One group of a cache statement: the layers that keep it, what each of
    them keeps a token (``sides``, as :class:`CacheSpec` has them), and for
    how long — ``window`` 0 keeps every token of a sequence, ``window=W``
    only what a query at the sequence's next position can still read: the
    ``W`` positions up to and including it.  A group is a pool of its own:
    buffers for its layers alone, an allocator, a block table by absolute
    position.

    ``state=True`` is the third retention: nothing a token.  ``sides`` then
    reads as the buffers of fixed size a *slot* keeps a layer, with no table
    and no blocks, and each side's arity says what kind it is: ``(rows,
    width)`` is a **shift** side — the last ``rows`` columns of a
    ``width``-wide product, which every token shifts by one, in the cache's
    dtype — and ``(heads, d_k, d_v)`` a **recurrent** side — a float32 matrix
    a head that the model's own recurrence maps forward with every token;
    the pool stores, snapshots and restores it and never computes it.  A
    group keeps one side of a kind at most.  What a prefix hit can reuse of
    such a group is a **snapshot** of all its sides that somebody kept at
    exactly the hit's end (``group_cache.StatePool``); ``stride`` is the
    distance between the snapshots a tail prefill leaves (the group's own:
    a snapshot costs what the state weighs; 0: the pool's default) and
    ``chunk`` the rows the model's scan of a recurrent side takes at a time
    (0: no scan), which the engine's spans count a tail in."""

    layers: Tuple[int, ...]
    sides: Tuple[Tuple[int, ...], ...]
    window: int = 0
    state: bool = False
    stride: int = 0
    chunk: int = 0


@dataclass(frozen=True)
class CacheSpec:
    """What a model caches a token a layer: the statement the engine builds
    its pool from (``model.cache_spec()``).

    ``sides`` is one ``(heads, width)`` per buffer a layer keeps.  A
    K/V-caching model states two sides of ``(kv_heads, head_dim)``
    (``kind="kv"``); a latent-attention model states ONE side of one "head"
    whose width is the latent vector's (``kind="latent"``): key and value at
    once, shared by every query head.  A model whose attention runs under a
    learned indexer states THREE: K and V per KV head and the indexer's one
    key a token (``kind="indexed"``; the third side has its own shape).  A
    model that keeps exact K and V only inside the current **window** and,
    for every window passed, one learned summary key and value a **chunk**
    states the K/V sides and the two sizes (``kind="windowed"``): the pool
    then has a second *group* — summary blocks of one window's
    ``window // chunk`` rows each, with an allocator and a table of their
    own — and the exact group's blocks are released behind the window.  The
    ``kind`` names the attention calls the model makes on its cache
    context.

    A model whose layers differ in what they keep states it **by layer**
    (:meth:`by_layer`): a :class:`CacheGroup` for each kind of layer, every
    layer in exactly one.  :attr:`groups` reads any statement that way: the
    four kinds above are one group of every layer that keeps every token —
    and ``windowed`` an exact group that keeps a window and a summary group
    that keeps all.  A group may also keep **state** instead of tokens
    (``CacheGroup.state``): a buffer of fixed size a slot, rewritten by every
    token, which only a statement by layer can name."""

    num_layers: int
    sides: Tuple[Tuple[int, int], ...]
    kind: str = "kv"
    #: ``kind="indexed"``: the tokens a query keeps; a context longer than
    #: this takes the indexed path
    topk: int = 0
    #: ``kind="windowed"``: positions of a window and of a chunk of it
    window: int = 0
    chunk: int = 0
    #: the statement by layer (:meth:`by_layer`); empty: one statement for
    #: every layer
    layer_groups: Tuple[CacheGroup, ...] = ()

    @property
    def groups(self) -> Tuple[CacheGroup, ...]:
        """The statement as groups, whatever its kind."""
        if self.layer_groups:
            return self.layer_groups
        every = tuple(range(self.num_layers))
        if self.kind == "windowed":
            return (CacheGroup(every, self.sides, self.window),
                    CacheGroup(every, self.sides))
        return (CacheGroup(every, self.sides),)

    @property
    def tail_limit(self) -> int:
        """The longest tail one prefill program takes (0: any).  A group
        that keeps a window holds a tail's blocks beside the window before
        it, so a longer prompt is prefilled in pieces: of a window
        (``windowed``, whose pieces each close one), or of two windows."""
        if self.kind == "windowed":
            return self.window
        return 2 * max((g.window for g in self.layer_groups), default=0)

    @classmethod
    def by_layer(cls, groups, kind: str = "kv") -> "CacheSpec":
        """A statement by layer: ``groups`` of :class:`CacheGroup`, which
        between them name every layer ``0..n-1`` once.  The attention calls
        are ``kind``'s; which keys a call reads is its layer's group's."""
        groups = tuple(CacheGroup(tuple(int(i) for i in g.layers),
                                  tuple(tuple(int(n) for n in side)
                                        for side in g.sides),
                                  int(g.window), bool(g.state), int(g.stride),
                                  int(g.chunk))
                       for g in groups)
        named = sorted(i for g in groups for i in g.layers)
        if not groups or named != list(range(len(named))):
            raise ValueError(f"the groups' layers {named} are not every "
                             f"layer 0..{len(named) - 1} once")
        if any(g.window < 0 for g in groups):
            raise ValueError("a group's window must be >= 0")
        for g in groups:
            arities = sorted(len(side) for side in g.sides)
            if g.state and (g.window or arities not in ([2], [3], [2, 3])):
                raise ValueError(
                    "a state group keeps one buffer of a kind a slot a layer "
                    "— a shift side (rows, width), a recurrent side (heads, "
                    "d_k, d_v) or one of each — and no window")
            if not g.state and (g.stride or arities != [2] * len(arities)):
                raise ValueError("a group that keeps tokens states (heads, "
                                 "width) sides and no snapshot stride")
            if g.stride < 0:
                raise ValueError("a group's snapshot stride must be >= 0")
            if g.chunk < 0 or (g.chunk and not (g.state and 3 in arities)):
                raise ValueError("a scan's chunk is stated beside a recurrent "
                                 "side, and is >= 0")
        if groups[0].state:
            raise ValueError("the first group counts the sequence's "
                             "positions in blocks: it is no state group")
        return cls(len(named), groups[0].sides, kind, layer_groups=groups)

    @classmethod
    def kv(cls, num_layers: int, kv_heads: int, head_dim: int) -> "CacheSpec":
        return cls(int(num_layers),
                   ((int(kv_heads), int(head_dim)),) * 2, "kv")

    @classmethod
    def latent(cls, num_layers: int, width: int) -> "CacheSpec":
        return cls(int(num_layers), ((1, int(width)),), "latent")

    @classmethod
    def indexed(cls, num_layers: int, kv_heads: int, head_dim: int,
                index_dim: int, topk: int) -> "CacheSpec":
        return cls(int(num_layers),
                   ((int(kv_heads), int(head_dim)),) * 2
                   + ((1, int(index_dim)),), "indexed", int(topk))

    @classmethod
    def windowed(cls, num_layers: int, kv_heads: int, head_dim: int,
                 window: int, chunk: int) -> "CacheSpec":
        if window % chunk:
            raise ValueError(f"chunk {chunk} must divide window {window}")
        return cls(int(num_layers), ((int(kv_heads), int(head_dim)),) * 2,
                   "windowed", window=int(window), chunk=int(chunk))


def cache_spec_of(model) -> CacheSpec:
    """The model's own statement; a model that makes none (a duck-typed
    decoder with a ``.config``) is taken to cache K and V per KV head."""
    stated = getattr(model, "cache_spec", None)
    if stated is not None:
        return stated()
    cfg = model.config
    return CacheSpec.kv(
        cfg.num_hidden_layers,
        getattr(cfg, "n_kv_heads", None) or cfg.num_attention_heads,
        cfg.head_dim)


class KVCache:
    """The speculative draft's cache (``SpecState.cache``): preallocated
    dense per-slot KV storage shared by all layers of the draft model.
    The engine's own cache is the paged pool, never this.

    Args:
        num_slots:    fixed decode batch width (continuous-batching slots).
        num_layers:   decoder layer count.
        max_seq:      cache capacity per slot (prompt + generated tokens).
        num_kv_heads: KV head count (``< num_heads`` under GQA).
        head_dim:     per-head dimension.
        dtype:        cache dtype (default float32; bf16 halves HBM).
    """

    def __init__(self, num_slots: int, num_layers: int, max_seq: int,
                 num_kv_heads: int, head_dim: int, dtype="float32"):
        if num_slots < 1 or num_layers < 1 or max_seq < 1:
            raise ValueError("num_slots/num_layers/max_seq must be >= 1")
        self.num_slots = int(num_slots)
        self.num_layers = int(num_layers)
        self.max_seq = int(max_seq)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype_mod.convert_dtype(dtype)
        shape = (self.num_slots, self.num_layers, self.max_seq,
                 self.num_kv_heads, self.head_dim)
        self.k = Tensor._wrap(jnp.zeros(shape, dtype=self.dtype))
        self.v = Tensor._wrap(jnp.zeros(shape, dtype=self.dtype))
        self.lengths = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        for t in (self.k, self.v, self.lengths):
            t.persistable = True

    # -- serving-loop state ops (called inside OR outside a trace) --------

    def prefill_write(self, layer_idx: int, slot, k, v) -> None:
        """Write a whole prompt's K/V into one slot at positions 0..S-1.

        ``k``/``v``: ``[1, S, Hkv, D]`` (S = prefill bucket ≤ max_seq);
        ``slot``: scalar int (may be traced — one compiled prefill serves
        every slot).
        """
        s = _as_i32(slot).reshape(())
        li = jnp.int32(layer_idx)
        zero = jnp.int32(0)
        for buf, new in ((self.k, k), (self.v, v)):
            arr = buf._value()
            upd = new._value().astype(arr.dtype)[:, None]   # [1,1,S,Hkv,D]
            arr = jax.lax.dynamic_update_slice(
                arr, upd, (s, li, zero, zero, zero))
            buf._set_data(arr)

    def set_length(self, slot, length) -> None:
        """Record a freshly prefilled slot's valid length (= prompt len)."""
        s = _as_i32(slot).reshape(())
        ln = _as_i32(length).reshape(())
        self.lengths._set_data(self.lengths._value().at[s].set(ln))

    def decode_write(self, layer_idx: int, k, v
                     ) -> Tuple[Tensor, Tensor, Tensor]:
        """Write one decode token per slot at that slot's current length.

        ``k``/``v``: ``[slots, 1, Hkv, D]``.  Returns the post-write layer
        caches ``[slots, max_seq, Hkv, D]`` and the pre-advance lengths
        ``[slots]`` — exactly what ``ops.cached_attention`` consumes.
        """
        lens = self.lengths._value()
        outs = []
        for buf, new in ((self.k, k), (self.v, v)):
            arr = buf._value()
            layer = arr[:, layer_idx]                       # [slots,T,Hkv,D]
            upd = new._value().astype(arr.dtype)            # [slots,1,Hkv,D]
            layer = jax.vmap(
                lambda c, u, p: jax.lax.dynamic_update_slice(
                    c, u, (p, jnp.int32(0), jnp.int32(0))))(layer, upd, lens)
            buf._set_data(arr.at[:, layer_idx].set(layer))
            outs.append(Tensor._wrap(layer))
        return outs[0], outs[1], Tensor._wrap(lens)

    def verify_write(self, layer_idx: int, k, v
                     ) -> Tuple[Tensor, Tensor, Tensor]:
        """Speculative verify write: W tokens per slot at that slot's
        positions ``lengths[slot] .. lengths[slot] + W - 1``.

        ``k``/``v``: ``[slots, W, Hkv, D]`` (W = k_draft + 1, a trace
        constant).  Returns the post-write layer caches
        ``[slots, max_seq, Hkv, D]`` and the window-start lengths
        ``[slots]`` — what ``ops.verify_attention`` consumes.  Writes
        past ``max_seq`` are scatter-dropped (a near-capacity slot's
        over-the-end window positions are junk the acceptance cap
        already makes unemittable — and unreadable, per the write
        discipline)."""
        lens = self.lengths._value()
        W = k.shape[1]
        rows = jnp.arange(self.num_slots, dtype=jnp.int32)[:, None]
        pos = lens[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        outs = []
        for buf, new in ((self.k, k), (self.v, v)):
            arr = buf._value()
            upd = new._value().astype(arr.dtype)        # [slots,W,Hkv,D]
            layer = arr[:, layer_idx]                   # [slots,T,Hkv,D]
            layer = layer.at[rows, pos].set(upd)        # OOB rows dropped
            buf._set_data(arr.at[:, layer_idx].set(layer))
            outs.append(Tensor._wrap(layer))
        return outs[0], outs[1], Tensor._wrap(lens)

    def verify_attention(self, layer_idx: int, q, k, v):
        """One verify-window step of attention for this layer: write the
        W-token window, then attend with the per-slot offset causal
        mask (``ops.verify_attention``)."""
        from ..ops.cached_attention import verify_attention

        k_full, v_full, lens = self.verify_write(layer_idx, k, v)
        return verify_attention(q, k_full, v_full, lens)

    def advance(self, active) -> None:
        """Grow lengths by one for active slots (call once per decode step,
        after all layers have written).  Speculative rounds pass
        ``active * accepted_count`` — the mask is added verbatim, so a
        multi-token advance rides the same op."""
        mask = _as_i32(active)
        self.lengths._set_data(self.lengths._value() + mask)

    # -- host-side management ---------------------------------------------

    def reset(self) -> None:
        """Forget all sequences (lengths → 0).  Cache payloads are left as
        is — the write discipline above makes stale bytes unreadable."""
        self.lengths._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))

    def nbytes(self) -> int:
        itemsize = jnp.zeros((), dtype=self.dtype).dtype.itemsize
        return 2 * self.num_slots * self.num_layers * self.max_seq * \
            self.num_kv_heads * self.head_dim * itemsize


@dataclass
class CacheContext:
    """Per-forward-call routing handle threaded through model layers.

    ``mode`` selects the path: ``"prefill"`` runs the normal causal forward
    while writing K/V into ``slot``; ``"decode"`` runs single-token cached
    attention for all slots at once; ``"verify"`` is the speculative-
    decoding verify window — ``width`` tokens per slot at each slot's own
    offset, one fixed-shape forward scoring every draft proposal at once
    (``width`` = k_draft + 1, a trace-time python constant).
    ``layer_idx`` is advanced by the model's layer loop (a per-trace
    python constant).  Models only duck-type this object, keeping
    ``models/`` free of serving imports.

    This base class is the handle over the draft's dense :class:`KVCache`
    (and over the pool for the target's verify window, which needs no
    paged-specific routing); :class:`~.paging.PagedCacheContext` extends
    it with the pool's prefill and decode routing.
    """

    cache: KVCache
    mode: str                           # "prefill" | "decode" | "verify"
    slot: Optional[Tensor] = None               # prefill: scalar int32
    length: Optional[Tensor] = None             # prefill: scalar int32
    active: Optional[Tensor] = None     # decode/verify: [slots] i32 mask
    layer_idx: int = 0
    width: int = 1                      # verify: tokens per slot (k+1)
    #: set by :meth:`select_last`: the model handed its head one row
    narrowed: bool = False
    #: int32 scalars an expert layer reports (:meth:`note_experts`), one
    #: pair a layer, in trace order
    expert_counts: Optional[list] = None
    #: int32 scalars an indexed-attention layer reports
    #: (:meth:`note_selection`), one pair a layer
    selection_counts: Optional[list] = None
    #: int32 scalars a windowed-attention layer reports (:meth:`note_rows`),
    #: one triple a layer: exact rows, summary rows, context
    row_counts: Optional[list] = None

    def __post_init__(self):
        if self.mode not in ("prefill", "decode", "verify"):
            raise ValueError(f"CacheContext mode {self.mode!r} "
                             "(want 'prefill', 'decode' or 'verify')")

    # -- what a model may ask besides attention ----------------------------

    def live_tokens(self, seq_len: int):
        """``[B, S]`` bool: the tokens of this call that a request owns.
        Decode: the active slots' one token; prefill: the bucket's rows
        below the prompt's real length.  An expert layer routes no other
        token (an idle slot or a pad row would read experts' weights for
        nothing and count as load)."""
        if self.mode == "prefill":
            real = _as_i32(self.length).reshape(()) - self._prefill_start()
            return (jnp.arange(seq_len, dtype=jnp.int32) < real)[None, :]
        live = _as_i32(self.active) > 0
        return jnp.broadcast_to(live[:, None], (live.shape[0], seq_len))

    def _prefill_start(self):
        return jnp.int32(0)

    def select_last(self, h):
        """Prefill: the hidden states ``[1, S, n]`` narrowed to the one row
        the engine samples from (the prompt's last real token), so that a
        wide head is applied to one row and not to the bucket.  Other modes
        return ``h`` as it is."""
        if self.mode != "prefill":
            return h
        idx = _as_i32(self.length).reshape(()) - self._prefill_start() - 1
        self.narrowed = True
        return Tensor._wrap(jax.lax.dynamic_slice_in_dim(
            h._value(), idx, 1, axis=1))

    def last_logits(self, logits, idx):
        """The logits row a prefill samples from: row ``idx`` of
        ``logits [1, S, V]``, or the one row there is when the model
        narrowed its head's input with :meth:`select_last`."""
        if self.narrowed:
            return logits[0, 0]
        return jax.lax.dynamic_index_in_dim(logits[0], idx, axis=0,
                                            keepdims=False)

    def with_expert_counts(self, tokens):
        """``tokens [slots]`` followed by what the model's layers counted in
        this call: ``[assignments_held, experts_touched, expert layers]``
        when it has expert layers, then ``[selected, context, indexed
        layers]`` when its attention runs under an indexer, or ``[exact rows,
        summary rows, context, windowed layers]`` when it keeps a window and
        summaries; ``tokens`` as they are when it has none of them."""
        for counts in (self.expert_counts, self.selection_counts,
                       self.row_counts):
            if counts:
                sums = [sum(c) for c in zip(*counts)]
                tokens = jnp.concatenate([tokens, jnp.stack(
                    sums + [jnp.int32(len(counts))]).astype(tokens.dtype)])
        return tokens

    def note_rows(self, exact, summary, context) -> None:
        """A windowed-attention layer's decode step (traced int32 scalars):
        the exact positions and the summary rows its running slots attended
        to, and the tokens those slots had."""
        if self.row_counts is None:
            self.row_counts = []
        self.row_counts.append((exact, summary, context))

    def note_selection(self, selected, context) -> None:
        """An indexed-attention layer's decode step (traced int32 scalars):
        the tokens its running slots attended to, and the tokens they had
        cached."""
        if self.selection_counts is None:
            self.selection_counts = []
        self.selection_counts.append((selected, context))

    def note_experts(self, assignments_held, experts_touched) -> None:
        """An expert layer's load in this call (traced int32 scalars): the
        decode program hands their sums over beside the tokens."""
        if self.expert_counts is None:
            self.expert_counts = []
        self.expert_counts.append((assignments_held, experts_touched))

    def write_prefill(self, k, v) -> None:
        self.cache.prefill_write(self.layer_idx, self.slot, k, v)

    def decode_attention(self, q, k, v):
        """One decode step of attention through the cache: write this
        layer's token K/V, then attend over the slot's valid window (the
        dense cache runs the masked one-row oracle; the paged context
        overrides the decode step with the pool's kernel-vs-reference
        routing).  In ``verify`` mode the same call site routes the
        W-token speculative window through the cache's
        ``verify_attention`` instead, so models need no
        speculation-specific branch at all."""
        if self.mode == "verify":
            return self.cache.verify_attention(self.layer_idx, q, k, v)
        from ..ops.cached_attention import cached_attention

        k_full, v_full, lens = self.cache.decode_write(self.layer_idx, k, v)
        return cached_attention(q, k_full, v_full, lens)

    def positions(self) -> Tensor:
        """Current token positions (pre-advance lengths) — position ids
        for learned embeddings / rotary offsets.  Decode: ``[slots, 1]``;
        verify: ``[slots, width]`` (each slot's window sits at its own
        offset ``lengths[slot] .. lengths[slot] + width - 1``)."""
        lens = self.cache.lengths._value()
        if self.mode == "verify":
            return Tensor._wrap(
                lens[:, None]
                + jnp.arange(self.width, dtype=jnp.int32)[None, :])
        return Tensor._wrap(lens[:, None])

    # -- prefill routing hooks (overridden by serving.PagedCacheContext) --

    def prefill_positions(self, seq_len: int) -> Optional[Tensor]:
        """Position ids for the prefill tokens, or None for the default
        ``0..S-1`` — the paged context offsets them past its cached
        prefix.  ``seq_len`` is a trace-time python constant."""
        return None

    def prefill_attention(self, q, k, v):
        """Prompt-forward attention.  Over the dense cache it is ordinary
        causal attention (GQA kv heads expanded first, exactly like the
        models' no-cache path); the paged context overrides this with a
        gather-by-block-table attention that also covers its cached
        prefix."""
        from ..ops.pallas import flash_attention

        B, S, H, _ = q.shape
        Hkv = k.shape[2]
        if Hkv != H:
            rep = H // Hkv
            D = q.shape[3]
            k = k.unsqueeze(3).expand([B, S, Hkv, rep, D]) \
                 .reshape([B, S, H, D])
            v = v.unsqueeze(3).expand([B, S, Hkv, rep, D]) \
                 .reshape([B, S, H, D])
        return flash_attention(q, k, v, is_causal=True, training=False)

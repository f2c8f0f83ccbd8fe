"""A paged cache of two groups with two lifetimes (``CacheSpec.windowed``).

The **exact group** is the K/V pool as it is (:class:`~.paging.PagedKVCache`:
same buffers, same writes, same allocator, copy-on-extend and block table by
absolute position).
What is new beside it:

- the **summary group**: one ``[num_summary_blocks, window // chunk, kv_heads,
  lanes]`` buffer a layer and side (pooled key, pooled value), with an
  allocator of its own and a table ``[slots, max_seq // window]`` by *window*.
  A block is the summaries of one whole window.
- **retention**: when a slot's length reaches the end of window ``w`` the
  window is *published* — its ``window // chunk`` summaries are computed from
  its exact blocks (:func:`~..ops.pallas.eva_attention_kernel.chunk_summaries`,
  float32, stored in the pool's dtype) into the slot's ``w``-th summary block
  — and the window's exact blocks are unreferenced: no later position reads
  them.  A tail prefill publishes the windows it closes inside its own
  program, a layer at a time before that layer's attention (the tail's later
  rows attend to them); a decode step that ends a window is followed by the
  engine's publishing program.  A slot's summary blocks, the ones its whole
  life can need, are taken at admission, so a sequence never waits for one
  while decoding.
- **two kinds of prefix hit** (:class:`WindowedPrefixCache`): whole windows
  by their summary blocks (a summary depends on its own chunk's keys and
  values alone, so it is as reusable as a block of K/V), then the blocks of
  the first window not covered by their exact blocks.  A hit that would end
  inside a window whose exact blocks are gone ends at the window's start.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..ops.pallas import eva_attention_kernel as eva
from .kv_cache import _as_i32
from .paging import (AllocatorError, BlockAllocator, PagedKVCache,
                     SCRATCH_BLOCK)
from .prefix_cache import PrefixCache, as_tokens

__all__ = ["WindowedKVCache", "WindowedPrefixCache"]

#: a hit of both kinds: summary block ids by window, then exact block ids of
#: the first window not covered
WindowHit = Tuple[List[int], List[int]]


class WindowedKVCache(PagedKVCache):
    """The exact group (the base class, unchanged) and the summary group."""

    def __init__(self, *args, window: int, chunk: int,
                 num_summary_blocks: Optional[int] = None,
                 num_blocks: Optional[int] = None, **kw):
        slots, max_seq = kw["num_slots"], kw["max_seq"]
        bs = kw.get("block_size", 16)
        if window % bs or max_seq % window or window % chunk:
            raise ValueError(
                f"window {window} must be whole blocks of {bs} and whole "
                f"chunks of {chunk}, and divide max_seq {max_seq}")
        if num_blocks is None:
            # every slot a window and a window-long tail, and a sequence's
            # length of room for what admission keeps for growth
            num_blocks = slots * 2 * (window // bs) + max_seq // bs + 1
        super().__init__(*args, num_blocks=num_blocks, **kw)
        self.window, self.chunk = int(window), int(chunk)
        self.window_blocks = self.window // self.block_size
        self.summary_rows = self.window // self.chunk
        self.max_windows = self.max_seq // self.window
        if num_summary_blocks is None:
            num_summary_blocks = self.num_slots * self.max_windows + 1
        self.num_summary_blocks = int(num_summary_blocks)
        self.summary_allocator = BlockAllocator(self.num_summary_blocks,
                                                reserved=1)
        heads, lanes = self.num_kv_heads, self.lane_dim
        #: ``summary_sides[s][layer]``: pooled keys, pooled values
        self.summary_sides = [
            [Tensor._wrap(jnp.zeros(
                (self.num_summary_blocks, self.summary_rows, heads, lanes),
                dtype=self.dtype)) for _ in range(self.num_layers)]
            for _ in range(2)]
        self.summary_tables = Tensor._wrap(jnp.full(
            (self.num_slots, self.max_windows), SCRATCH_BLOCK,
            dtype=jnp.int32))
        for t in (*self.summary_buffers(), self.summary_tables):
            t.persistable = True
        #: summary blocks each slot holds a ref on, by window
        self._slot_windows: List[List[int]] = [[] for _ in range(slots)]
        #: windows of each slot whose summaries are written
        self._published = [0] * slots
        self.exact_blocks_released = 0

    def summary_buffers(self) -> List[Tensor]:
        return [buf for side in self.summary_sides for buf in side]

    # -- host-side slot lifecycle -----------------------------------------

    def begin_sequence(self, slot: int, shared, prefix_len: int,
                       tail_bucket: int, *, total: int = 0,
                       reserve: int = 0, write: bool = True) -> bool:
        """Storage of one admission, all or nothing: refs on the hit's
        summary and exact blocks, fresh exact blocks for the tail bucket,
        and a fresh summary block for every window a sequence of ``total``
        tokens (prompt and all it may generate) can close.  False (slot
        untouched) when either group cannot supply them, or when the exact
        group would be left with fewer than ``reserve`` blocks to get: what
        the running sequences may still grow by."""
        if self._slot_blocks[slot] or self._slot_windows[slot]:
            raise AllocatorError(f"slot {slot} already owns blocks")
        windows, exact = (list(x) for x in (shared or ([], [])))
        bs, wb = self.block_size, self.window_blocks
        if prefix_len != len(windows) * self.window + len(exact) * bs:
            raise ValueError(f"prefix_len {prefix_len} is not {len(windows)} "
                             f"windows + {len(exact)} blocks")
        if tail_bucket % bs:
            raise ValueError(f"tail bucket {tail_bucket} not a multiple of "
                             f"block_size {bs}")
        n_tail = tail_bucket // bs
        first = len(windows) * wb               # blocks behind the summaries
        if first + len(exact) + n_tail > self.max_blocks_per_slot:
            raise ValueError("prefix + tail blocks exceed max_blocks_per_slot")
        n_new = max(0, min(int(total) // self.window, self.max_windows)
                    - len(windows))
        # pin the hit before allocating: alloc() may evict idle cached blocks
        for al, ids in ((self.summary_allocator, windows),
                        (self.allocator, exact)):
            for b in ids:
                al.ref(int(b))
        fresh_w = self.summary_allocator.alloc(n_new)
        fresh = None if fresh_w is None else self.allocator.alloc(n_tail)
        if fresh is not None and self.available_blocks() < reserve:
            for b in fresh:
                self.allocator.unref(b)
            fresh = None
        if fresh is None:
            for al, ids in ((self.summary_allocator,
                             windows + (fresh_w or [])),
                            (self.allocator, exact)):
                for b in ids:
                    al.unref(int(b))
            return False
        owned = [SCRATCH_BLOCK] * first + exact + fresh
        self._slot_blocks[slot] = owned
        self._slot_windows[slot] = windows + fresh_w
        self._published[slot] = len(windows)
        if write:
            self._set_row(slot, owned)
            self._set_row(slot, self._slot_windows[slot], self.summary_tables)
        return True

    def tables(self) -> List[Tensor]:
        return [self.block_tables, self.summary_tables]

    def table_rows(self, slot: int):
        return super().table_rows(slot) + [
            self._row(self._slot_windows[slot], self.max_windows)]

    def release_slot(self, slot: int) -> None:
        held, self._slot_windows[slot] = self._slot_windows[slot], []
        for b in held:
            self.summary_allocator.unref(b)
        if held:
            self._set_row(slot, [], self.summary_tables)
        self._published[slot] = 0
        super().release_slot(slot)

    def windows_pending(self, slot: int, seq_len: int) -> int:
        """Windows of ``slot`` that ``seq_len`` cached tokens have closed and
        whose summaries are not written yet."""
        return seq_len // self.window - self._published[slot]

    def published(self, slot: int) -> int:
        return self._published[slot]

    def release_windows(self, slot: int, seq_len: int, *,
                        write: bool = True) -> int:
        """Host half of publishing: the windows ``seq_len`` tokens have
        closed are marked published (their summaries were just written by a
        program) and their exact blocks are unreferenced.  Returns the exact
        blocks the slot let go of."""
        done = min(seq_len // self.window, len(self._slot_windows[slot]))
        if done <= self._published[slot]:
            return 0
        owned = self._slot_blocks[slot]
        lo, hi = self._published[slot] * self.window_blocks, \
            min(done * self.window_blocks, len(owned))
        drop = [b for b in owned[lo:hi] if b != SCRATCH_BLOCK]
        owned[lo:hi] = [SCRATCH_BLOCK] * (hi - lo)
        self._published[slot] = done
        if write:
            self._set_row(slot, owned)
        for b in drop:
            self.allocator.unref(b)
        self.exact_blocks_released += len(drop)
        return len(drop)

    def owned_blocks(self, slot: int) -> WindowHit:
        """``(summary block ids of the published windows, exact block ids by
        position, released ones as the scratch block)``: what the prefix
        cache registers."""
        return (self._slot_windows[slot][:self._published[slot]],
                self._slot_blocks[slot])

    def shorten_hit(self, shared: WindowHit) -> Tuple[int, WindowHit]:
        windows, exact = list(shared[0]), list(shared[1])
        if exact:
            exact.pop()
        else:
            windows.pop()
        return (len(windows) * self.window + len(exact) * self.block_size,
                (windows, exact))

    def warm_host_programs(self) -> None:
        super().warm_host_programs()
        self._set_row(0, [], self.summary_tables)

    def nbytes(self) -> int:
        return super().nbytes() + sum(int(b._value().nbytes)
                                      for b in self.summary_buffers())

    def summary_blocks_in_use(self) -> int:
        s = self.summary_allocator.stats()
        return s["used"] + s["cached"]

    def check_invariants(self) -> List[str]:
        out = super().check_invariants()
        out += [f"summary group: {v}" for v in self.summary_allocator.check()]
        for slot, held in enumerate(self._slot_windows):
            for b in held:
                if self.summary_allocator.refcount(b) < 1:
                    out.append(f"slot {slot} holds freed summary block {b}")
        return out

    # -- work a decode step costs (the engine's ``decode_chunks``) ----------

    def decode_items_fn(self):
        if super().decode_items_fn() is None:
            return None
        arr = self.sides[0][0]._value()
        ct = eva.exact_chunk_tokens(arr.shape, arr.dtype.itemsize,
                                    self.window)
        window = self.window
        return lambda seq_len: int(eva.decode_items(
            seq_len, window=window, chunk_tokens=ct))

    # -- traced ops ---------------------------------------------------------

    def _rows(self, slot):
        s = _as_i32(slot).reshape(())
        return tuple(jax.lax.dynamic_index_in_dim(t._value(), s, axis=0,
                                                  keepdims=False)
                     for t in (self.block_tables, self.summary_tables))

    def publish(self, layer_idx: int, slot, window_idx, keep, phi, mu) -> None:
        """Summaries of window ``window_idx`` of ``slot`` (traced scalars)
        from this layer's exact blocks into the slot's summary block of that
        window; into the scratch block where ``keep`` is false."""
        row, srow = self._rows(slot)
        w = _as_i32(window_idx).reshape(())
        D = self.head_dim
        k_l, v_l = (self.sides[i][layer_idx]._value() for i in (0, 1))
        ks, vs = eva.chunk_summaries(
            eva.window_rows(k_l, row, w, window=self.window)[..., :D],
            eva.window_rows(v_l, row, w, window=self.window)[..., :D],
            phi, mu, chunk=self.chunk, scale=D ** -0.5)
        sid = jnp.where(keep, jnp.take(srow, jnp.clip(
            w, 0, self.max_windows - 1)), SCRATCH_BLOCK)
        for buf, new in zip((self.summary_sides[0][layer_idx],
                             self.summary_sides[1][layer_idx]), (ks, vs)):
            arr = buf._value()
            with jax.named_scope(eva.SUMMARISE_SCOPE):
                buf._set_data(arr.at[sid].set(
                    self._to_lanes(new, arr.dtype, arr.shape[-1])))

    def _summary_layers(self, layer_idx: int):
        return (self.summary_sides[0][layer_idx]._value(),
                self.summary_sides[1][layer_idx]._value())

    def windowed_prefill_attention(self, layer_idx: int, slot, q, k, v, phi,
                                   mu, start, length):
        """A tail's whole step for this layer: write its K and V (the K/V
        pool's own write), publish the window the tail closes, then attend:
        ``q [1, S, H, D]`` rows see the exact keys of their own window and
        the summaries of every window before it.  A tail is one window long
        at most (the engine prefills a longer one a window at a time), so it
        passes one window's end at most.  A prompt of one window or less is
        the same call with no summary item (the dense tail kernel's 16-key
        grid steps do not fit 32 KV heads of 128 in VMEM: 49.9 MB of the 48
        a v5e program may scope)."""
        S, D = q.shape[1], q.shape[3]
        if S > self.window:
            raise ValueError(f"a tail of {S} rows is longer than the window "
                             f"of {self.window}")
        self.prefill_write(layer_idx, slot, k, v, start)
        st = _as_i32(start).reshape(())
        ln = _as_i32(length).reshape(())
        w = st // self.window
        self.publish(layer_idx, slot, w, (w + 1) * self.window <= ln, phi, mu)
        k_l, v_l = (self.sides[i][layer_idx]._value() for i in (0, 1))
        ks_l, vs_l = self._summary_layers(layer_idx)
        row, srow = self._rows(slot)

        qp = self._to_lanes(q._value()[0], k_l.dtype, k_l.shape[-1])
        args = (qp, k_l, v_l, ks_l, vs_l, row, srow, st)
        kw = dict(window=self.window, scale=D ** -0.5)
        with jax.named_scope(eva.ATTEND_SCOPE):
            out = (eva.eva_paged_prefill(*args, interpret=self._interpret,
                                         **kw)
                   if self.kernel == "pallas"
                   else eva.eva_prefill_reference(*args, **kw))
        return Tensor._wrap(out[None, :, :, :D].astype(q.dtype))

    def windowed_decode_attention(self, layer_idx: int, q, k, v, active):
        """One decode step for this layer: write each slot's K and V, then
        attend — a slot inside its first window has no summary item, and the
        kernel's work on it is the K/V decode kernel's.  Returns ``(out
        [slots, 1, H, D], exact rows, summary rows, context)``: int32 scalars
        over the running slots."""
        k_l, v_l, tbl, lens = self._decode_token_write(layer_idx, k, v)
        ks_l, vs_l = self._summary_layers(layer_idx)
        act = _as_i32(active)
        live = act > 0
        D = q.shape[3]
        passed = lens // self.window
        counts = tuple(jnp.sum(jnp.where(live, n, 0)) for n in (
            lens - passed * self.window + 1, passed * self.summary_rows,
            lens + 1))
        qp = self._to_lanes(q._value()[:, 0], q.dtype, k_l.shape[-1])
        args = (qp, k_l, v_l, ks_l, vs_l, tbl, self.summary_tables._value(),
                lens, act)
        kw = dict(window=self.window, scale=D ** -0.5)
        with jax.named_scope(eva.ATTEND_SCOPE):
            out = (eva.eva_paged_decode(*args, interpret=self._interpret,
                                        **kw)
                   if self.kernel == "pallas"
                   else eva.eva_decode_reference(*args, **kw))
        return (Tensor._wrap(out[:, None, :, :D].astype(q.dtype)), *counts)


class WindowedPrefixCache:
    """The prefix cache of a :class:`WindowedKVCache`: whole windows by their
    summary blocks, then the blocks of the first window not covered by their
    exact blocks — two :class:`~.prefix_cache.PrefixCache` chains over the two
    allocators, keyed by the same chain hash (a window's key is the hash at
    its end).  The engine's interface is the one cache's."""

    def __init__(self, cache: WindowedKVCache):
        self.cache = cache
        self.windows = PrefixCache(cache.summary_allocator, cache.window)
        self.exact = PrefixCache(cache.allocator, cache.block_size)

    @property
    def epoch(self) -> int:
        return self.exact.epoch

    def _exact_span(self, n_windows: int):
        """Where the exact walk starts and how far it may go: never a whole
        window, so that a tail always passes the window's end and publishes
        it."""
        return dict(first_block=n_windows * self.cache.window_blocks,
                    max_blocks=self.cache.window_blocks - 1)

    def lookup(self, prompt, count: bool = True, salt: bytes = b"",
               keys=None):
        """``(n_tokens, (summary block ids, exact block ids))``; ``keys``:
        the prompt's :class:`~.prefix_cache.ChainKeys` where the caller keeps
        them (both chains', each under its block size)."""
        n_w, windows = self.windows.lookup(prompt, count=False, salt=salt,
                                           keys=keys)
        n_e, exact = self.exact.lookup(prompt, count=False, salt=salt,
                                       keys=keys,
                                       **self._exact_span(len(windows)))
        if count:
            self.record_lookup(len(prompt), n_w + n_e)
        return n_w + n_e, (windows, exact)

    def probe(self, prompt, salt: bytes = b"") -> int:
        prompt = as_tokens(prompt)
        n_w = self.windows.probe(prompt, salt=salt)
        return n_w + self.exact.probe(
            prompt, salt=salt,
            **self._exact_span(n_w // self.cache.window))

    def record_lookup(self, prompt_tokens: int, hit_tokens: int) -> None:
        self.exact.record_lookup(prompt_tokens, hit_tokens)

    def register(self, prompt, owned: WindowHit, salt: bytes = b"",
                 keys=None) -> int:
        """The prompt's whole published windows, and the whole blocks of its
        last, unfinished window while the slot still holds them."""
        windows, exact = owned
        n = self.windows.register(prompt, windows, salt=salt, keys=keys)
        first = (len(prompt) // self.cache.window) * self.cache.window_blocks
        return n + self.exact.register(prompt, exact, salt=salt,
                                       first_block=first, keys=keys)

    def bump_epoch(self) -> int:
        self.windows.bump_epoch()
        return self.exact.bump_epoch()

    def clear(self) -> int:
        return self.windows.clear() + self.exact.clear()

    def __len__(self) -> int:
        return len(self.windows) + len(self.exact)

    def hit_rate(self) -> float:
        return self.exact.hit_rate()

    def stats(self) -> dict:
        s, w = self.exact.stats(), self.windows.stats()
        s["window_entries"] = w["entries"]
        s["window_evictions"] = w["evictions"]
        return s

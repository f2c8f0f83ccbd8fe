"""A paged cache stated by layer (``CacheSpec.by_layer``): one K/V pool a
group of layers, each with its own buffers, allocator and block table by
absolute position, under one set of lengths.

A model whose layers differ in what they keep — some attend over the whole
sequence, the others over a window behind the query — states a
:class:`~.kv_cache.CacheGroup` for each kind.  A group is a
:class:`~.paging.PagedKVCache` of its layers alone (same writes, same
attention calls, same copy-on-extend); what this module adds is the cache of
several of them:

- **retention is the group's.**  A group with ``window=W`` lets a block go as
  soon as no live position can read it: every key in it lies more than ``W -
  1`` positions behind the sequence's next query
  (:meth:`~.paging.PagedKVCache.release_behind`, called after a tail prefill
  with the prompt's end and before every decode step with the slot's
  length).  Inside a tail prefill the blocks behind the tail's *first*
  query's window are still held: they go at its end.  The attention calls of
  that group's layers mask by ``W`` and visit no block behind it.
- **admission is by every group**: the tail bucket's blocks from each, and
  each left with what the running sequences (and this one) may still take —
  the rest of a sequence's length from a group that keeps all, a window and
  the block being written from one that keeps ``W``.  A request is deferred,
  never failed, for want of either.
- **a prefix hit of two kinds** (:class:`GroupedPrefixCache`): it ends at
  the longest registered length at which every group that keeps all has every
  block and every group with a window has the blocks of the ``W - 1``
  positions before it — what the tail's first query reads.  Where the window
  group's blocks are gone the hit is shortened to the longest end that has
  them, possibly none.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .kv_cache import CacheGroup
from .paging import PagedKVCache, SCRATCH_BLOCK
from .prefix_cache import PrefixCache

__all__ = ["GroupedKVCache", "GroupedPrefixCache"]

#: a hit: block ids by absolute position, one list a group (the scratch block
#: where a group with a window needs none)
GroupHit = Tuple[List[int], ...]


class GroupedKVCache:
    """The groups' pools behind the one cache's surface."""

    def __init__(self, groups: Sequence[CacheGroup], *, num_slots: int,
                 max_seq: int, dtype, block_size: int = 16,
                 num_blocks: Sequence[Optional[int]] = (),
                 kernel: str = "reference"):
        sizes = list(num_blocks) + [None] * (len(groups) - len(num_blocks))
        self.groups = tuple(groups)
        self.pools: List[PagedKVCache] = []
        for g, n in zip(self.groups, sizes):
            if g.window and n is None:
                # every slot a window and the block being written, and two
                # of the longest tail beside a hit's window
                per = -(-g.window // block_size) + 1
                n = num_slots * per + per + 4 * g.window // block_size + 1
            self.pools.append(PagedKVCache(
                num_slots, len(g.layers), max_seq, sides=g.sides, dtype=dtype,
                block_size=block_size, num_blocks=n, kernel=kernel,
                window=g.window))
        first = self.pools[0]
        for p in self.pools[1:]:
            p.lengths = first.lengths       # one sequence, one length
        #: layer -> (its group's pool, its index among the group's layers)
        self._where = {layer: (p, i) for g, p in zip(self.groups, self.pools)
                       for i, layer in enumerate(g.layers)}
        self.num_layers = len(self._where)
        self.block_size = first.block_size
        self.max_blocks_per_slot = first.max_blocks_per_slot
        self.kernel = kernel
        self.lengths = first.lengths
        #: admissions each group refused for want of blocks
        self.deferred_by = [0] * len(self.pools)
        #: what the engine's gauges and the first group's prefix chain read
        self.allocator = first.allocator
        self.num_blocks = first.num_blocks

    # -- the pools as one ---------------------------------------------------

    def buffers(self):
        return [b for p in self.pools for b in p.buffers()]

    def nbytes(self) -> int:
        return sum(p.nbytes() for p in self.pools)

    @property
    def copy_on_extends(self) -> int:
        return sum(p.copy_on_extends for p in self.pools)

    def usable_blocks(self) -> int:
        return min(p.usable_blocks() for p in self.pools)

    def group_stats(self) -> List[dict]:
        """A group a row: its layers, its window, its blocks and how many a
        live slot holds."""
        return [{"layers": len(g.layers), "window": g.window,
                 "blocks": p.num_blocks - p.allocator.reserved,
                 "used": p.allocator.used_blocks,
                 "cached_idle": p.allocator.idle_cached_blocks,
                 "released": p.blocks_released,
                 "alloc_failures": p.allocator.alloc_failures}
                for g, p in zip(self.groups, self.pools)]

    def warm_host_programs(self) -> None:
        for p in self.pools:
            p.warm_host_programs()

    def check_invariants(self) -> List[str]:
        return [f"group {i} (window {p.kv_window}): {v}"
                for i, p in enumerate(self.pools)
                for v in p.check_invariants()]

    def decode_chunk_tokens(self) -> Optional[int]:
        return self.pools[0].decode_chunk_tokens()

    def decode_items_fn(self):
        """Work items a layer, the layers' mean: a group with a window lists
        only the chunks that meet it."""
        fns = [p.decode_items_fn() for p in self.pools]
        if fns[0] is None:
            return None
        shares = [len(g.layers) / self.num_layers for g in self.groups]
        return lambda seq_len: round(sum(
            s * f(seq_len) for s, f in zip(shares, fns)), 2)

    # -- host-side slot lifecycle ---------------------------------------------

    def begin_sequence(self, slot: int, shared, prefix_len: int,
                       tail_bucket: int, *, total: int = 0,
                       reserve: Sequence[int] = ()) -> bool:
        """One admission's storage from every group, all or nothing."""
        shared = shared or ([],) * len(self.pools)
        reserve = list(reserve) or [0] * len(self.pools)
        for i, (p, blocks, r) in enumerate(zip(self.pools, shared, reserve)):
            if not p.begin_sequence(slot, blocks, prefix_len, tail_bucket,
                                    total=total, reserve=r):
                self.deferred_by[i] += 1
                for q in self.pools[:i]:
                    q.release_slot(slot)
                return False
        return True

    def growth_needs(self, slot: int, total: int) -> List[int]:
        return [p.growth_need(slot, total) for p in self.pools]

    def extend_tail(self, slot: int, start: int, tail_bucket: int) -> bool:
        return all(p.extend_tail(slot, start, tail_bucket)
                   for p in self.pools)

    def ensure_capacity(self, slot: int, next_pos: int) -> bool:
        return all(p.ensure_capacity(slot, next_pos) for p in self.pools)

    def release_behind(self, slot: int, next_pos: int) -> int:
        return sum(p.release_behind(slot, next_pos) for p in self.pools)

    def release_slot(self, slot: int) -> None:
        for p in self.pools:
            p.release_slot(slot)

    def reset(self) -> None:
        for p in self.pools:
            p.reset()

    def owned_blocks(self, slot: int) -> GroupHit:
        return tuple(p.owned_blocks(slot) for p in self.pools)

    # -- traced state ops (the K/V pool's, by the layer's group) -------------

    def set_length(self, slot, length) -> None:
        self.pools[0].set_length(slot, length)

    def advance(self, active) -> None:
        self.pools[0].advance(active)

    def prefill_write(self, layer_idx: int, slot, k, v, start=0) -> None:
        pool, i = self._where[layer_idx]
        pool.prefill_write(i, slot, k, v, start)

    def dense_prefill_attention(self, layer_idx: int, slot, q, start,
                                length=None):
        pool, i = self._where[layer_idx]
        return pool.dense_prefill_attention(i, slot, q, start, length)

    def decode_attention(self, layer_idx: int, q, k, v, active):
        pool, i = self._where[layer_idx]
        return pool.decode_attention(i, q, k, v, active)


class GroupedPrefixCache:
    """The prefix cache of a :class:`GroupedKVCache`: one
    :class:`~.prefix_cache.PrefixCache` a group over its allocator, all keyed
    by the same chain hash from the prompt's start.  A group that keeps all
    is a chain (a hit is a contiguous prefix, leaves are evicted first); a
    group with a window keeps runs of entries that start in mid-prompt and is
    evicted oldest first.  The engine's interface is the one cache's."""

    def __init__(self, cache: GroupedKVCache):
        self.cache = cache
        self.block_size = cache.block_size
        self.chains = [PrefixCache(p.allocator, p.block_size,
                                   chained=not p.kv_window)
                       for p in cache.pools]
        #: hits that ended short of what the groups that keep all had, for
        #: want of a window's blocks
        self.hits_shortened = 0

    @property
    def epoch(self) -> int:
        return self.chains[0].epoch

    def _first_needed(self, pool: PagedKVCache, end: int) -> int:
        """The first block a tail that starts at block ``end`` reads of
        ``pool``: block 0, or the block of its first query's oldest key."""
        return max(0, end * self.block_size - pool.kv_window + 1) \
            // self.block_size if pool.kv_window else 0

    def _walk(self, prompt, salt: bytes, max_tokens: Optional[int]):
        """``(end, kept, keys)``: the hit's end in blocks, the end the groups
        that keep all would allow, and the chain keys up to there."""
        prompt = np.asarray(list(prompt), dtype=np.int64).reshape(-1)
        bs = self.block_size
        stop = max(0, (int(prompt.size) - 1) // bs)
        if max_tokens is not None:
            stop = min(stop, int(max_tokens) // bs)
        keys = self.chains[0]._keys_for(prompt, stop, salt)
        kept = stop
        for chain, pool in zip(self.chains, self.cache.pools):
            if not pool.kv_window:
                n = 0
                while n < kept and keys[n] in chain._entries:
                    n += 1
                kept = n
        # the longest end at which every window group has what the tail's
        # first query reads: a run of entries up to the end, counted once
        ok = [True] * (kept + 1)
        for chain, pool in zip(self.chains, self.cache.pools):
            if not pool.kv_window:
                continue
            run = 0
            for end in range(1, kept + 1):
                run = run + 1 if keys[end - 1] in chain._entries else 0
                ok[end] = ok[end] and \
                    run >= end - self._first_needed(pool, end)
        end = max(e for e in range(kept + 1) if ok[e])
        return end, kept, keys

    def lookup(self, prompt, count: bool = True, salt: bytes = b"",
               max_tokens: Optional[int] = None):
        """``(n_tokens, block ids by position a group)``; ``max_tokens``
        caps the hit's end."""
        end, kept, keys = self._walk(prompt, salt, max_tokens)
        self.hits_shortened += end < kept
        hit = []
        for chain, pool in zip(self.chains, self.cache.pools):
            first = self._first_needed(pool, end)
            ids = [SCRATCH_BLOCK] * first
            for key in keys[first:end]:
                e = chain._entries[key]
                e.hits += 1
                chain._entries.move_to_end(key)
                ids.append(e.block_id)
            hit.append(ids)
        if count:
            self.record_lookup(len(prompt), end * self.block_size)
        return end * self.block_size, tuple(hit)

    def probe(self, prompt, salt: bytes = b"") -> int:
        return self._walk(prompt, salt, None)[0] * self.block_size

    def record_lookup(self, prompt_tokens: int, hit_tokens: int) -> None:
        self.chains[0].record_lookup(prompt_tokens, hit_tokens)

    def register(self, prompt, owned: GroupHit, salt: bytes = b"",
                 hit_tokens: int = 0) -> int:
        """The prompt's whole blocks of a group that keeps all; of a group
        with a window the ones the slot still holds: its last window's.
        ``hit_tokens``: where the hit this sequence was admitted behind
        ended.  A window group keeps the last window of what it has seen: the
        run of blocks that hit read is dropped (where no slot holds it) once
        the sequence has registered a run that starts at or past its end —
        it has moved a whole window on, and a prompt that reaches the old end
        goes on to the new one.  (A document made resident in pieces would
        otherwise leave a window a piece behind it, and the oldest document's
        last window would be the first to go.)"""
        n, hit_end = 0, int(hit_tokens) // self.block_size
        for chain, pool, blocks in zip(self.chains, self.cache.pools, owned):
            first = next((i for i, b in enumerate(blocks)
                          if b != SCRATCH_BLOCK), len(blocks))
            n += chain.register(prompt, blocks, salt=salt, first_block=first)
            if pool.kv_window and 0 < hit_end <= first:
                keys = chain._keys_for(np.asarray(
                    list(prompt), dtype=np.int64).reshape(-1), hit_end, salt)
                # from where the run of a prompt that ended there starts:
                # what the longest hit of that very prompt reads
                for key in keys[self._first_needed(pool, hit_end - 1):]:
                    e = chain._entries.get(key)
                    if e is not None and \
                            pool.allocator.refcount(e.block_id) == 1:
                        chain._evict_one(key)
        return n

    def bump_epoch(self) -> int:
        return [c.bump_epoch() for c in self.chains][0]

    def clear(self) -> int:
        return sum(c.clear() for c in self.chains)

    def __len__(self) -> int:
        return sum(len(c) for c in self.chains)

    def hit_rate(self) -> float:
        return self.chains[0].hit_rate()

    def stats(self) -> dict:
        s = self.chains[0].stats()
        s["group_entries"] = [len(c) for c in self.chains]
        s["group_evictions"] = [c.evictions for c in self.chains]
        s["hits_shortened"] = self.hits_shortened
        return s

"""The engine's KV cache: a block-granular pool behind the CacheContext
surface.

Instead of reserving a ``max_seq`` stripe per slot (HBM sized for the
worst-case sequence, as the draft's dense :class:`~.kv_cache.KVCache`
does), the pool stores K/V in a fixed set of blocks — one
``[num_blocks, block_size, kv_heads, lane_dim]`` buffer per layer and per
side, the paged kernels' own operand (``lane_dim`` is ``head_dim`` rounded
up to the 128 lanes of a vector register, see :data:`LANES`), so a
compiled program writes each in place and reads it as it is — and
addresses them through per-slot int32 block tables of fixed shape
``[slots, max_blocks_per_slot]``.  Two things fall out:

- **HBM scales with live tokens, not worst-case slots** — the same pool
  holds many more concurrent sequences when most are short; and
- **blocks are refcountable**, so identical prompt prefixes across
  requests (system prompts, few-shot headers) can share storage via
  :class:`~.prefix_cache.PrefixCache` instead of being re-prefilled.

The zero-recompile invariant survives because every compiled shape is a
function of ``(slots, bucket, block_size, max_blocks_per_slot)`` only:
block ids live *inside* the block-table tensor (device state threaded
through traces exactly like optimizer state), and all
allocation/eviction/copy-on-extend happens host-side between steps,
changing argument *values* only.

Write discipline (the dense cache's contract, block-indirect):
prefill writes whole tail-bucket blocks starting at the block boundary
``start_pos // block_size``; decode writes each slot's token at
``lengths[slot]`` through the table; attention reads positions
``<= lengths[slot]`` via gather-by-block-table.  Block 0 is a reserved
scratch block: idle slots' table rows point at it, so the all-slots
fixed-shape decode write never touches a live block.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..core import dtype as dtype_mod
from ..ops.cached_attention import (
    block_prefill_attention, cached_attention, gather_block_kv,
    paged_decode_attention, paged_prefill_attention, verify_attention,
)
from ..ops.pallas.paged_attention_kernel import (
    blocks_need_kernel_write, write_blocks)
from .kv_cache import CacheContext, _as_i32

__all__ = ["BlockAllocator", "PagedKVCache", "PagedCacheContext",
           "AllocatorError"]

#: Block id every idle/retired slot's table points at.  Never allocated.
SCRATCH_BLOCK = 0

#: Lanes of a TPU vector register.  The pool's minor dim is ``head_dim``
#: rounded up to a multiple of it, the pad lanes zero and never read back:
#: a Pallas kernel's operand is row-major with its minor dim tiled to 128
#: lanes, while XLA:TPU *stores* an array whose minor dim is narrower with
#: another dim minor-most (bf16 ``[2049, 16, 16, 64]`` as ``{0,3,2,1}``), and
#: then converts the whole buffer to the kernel's form and back in every
#: program that touches it.  Storing the lanes the operand has anyway makes
#: the stored form the operand: no copy, and nothing held twice.
LANES = 128

#: named scope of the pool's traffic in a compiled program's op names: the
#: scatter that writes new K/V through the block table into a layer's buffer
#: (the read is the buffer itself)
KV_WRITE_SCOPE = "kv.write"


class AllocatorError(RuntimeError):
    """A block-accounting invariant was about to be violated (double
    free, unref of a free block, ...).  The engine surfaces this as an
    unhealthy state instead of corrupting the pool silently."""


class BlockAllocator:
    """Host-side accounting for the fixed KV block pool.

    Blocks move between three disjoint states (plus the reserved scratch
    block): **free** (refcount 0, on the free list), **used** (referenced
    by at least one live slot), and **cached** (idle but retained by the
    prefix cache, which holds their single ref).  ``free + used + cached
    == total - reserved`` at every step — :meth:`check` verifies it and
    :meth:`stats` exports the gauges.

    When the free list runs dry, :meth:`alloc` asks ``evict_cb`` (wired
    to :meth:`PrefixCache._evict_for_alloc`) to release idle cached
    blocks, LRU-first.
    """

    def __init__(self, num_blocks: int, reserved: int = 1):
        if num_blocks < reserved + 1:
            raise ValueError(f"num_blocks must be > reserved={reserved}, "
                             f"got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.reserved = int(reserved)
        self._free: deque = deque(range(self.reserved, self.num_blocks))
        self._ref = [0] * self.num_blocks
        self._cached = set()            # block ids retained by PrefixCache
        #: cached blocks whose one ref is the prefix cache's (kept as the
        #: refs and marks move, so that admission and the step span read the
        #: pool's state without a walk over it)
        self._idle = 0
        self.evict_cb: Optional[Callable[[int], int]] = None
        # counters
        self.allocs = 0
        self.frees = 0
        self.alloc_failures = 0

    # -- core ops ----------------------------------------------------------

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` blocks (refcount 1 each).  Evicts idle cached blocks
        under pressure; returns None (all-or-nothing) if the pool cannot
        supply ``n`` blocks even after eviction."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if len(self._free) < n and self.evict_cb is not None:
            self.evict_cb(n - len(self._free))
        if len(self._free) < n:
            self.alloc_failures += 1
            return None
        out = []
        for _ in range(n):
            b = self._free.popleft()
            self._ref[b] = 1
            out.append(b)
        self.allocs += n
        return out

    def ref(self, block_id: int) -> int:
        b = self._check_id(block_id)
        if self._ref[b] < 1:
            raise AllocatorError(f"ref of free block {b}")
        self._ref[b] += 1
        if self._ref[b] == 2 and b in self._cached:
            self._idle -= 1
        return self._ref[b]

    def unref(self, block_id: int) -> int:
        b = self._check_id(block_id)
        if self._ref[b] < 1:
            raise AllocatorError(f"double free of block {b}")
        self._ref[b] -= 1
        if self._ref[b] == 1 and b in self._cached:
            self._idle += 1
        if self._ref[b] == 0:
            if b in self._cached:
                raise AllocatorError(
                    f"cached block {b} dropped to refcount 0: the prefix "
                    "cache must hold one ref per cached block")
            self._free.append(b)
            self.frees += 1
        return self._ref[b]

    def refcount(self, block_id: int) -> int:
        return self._ref[self._check_id(block_id)]

    def _check_id(self, block_id: int) -> int:
        b = int(block_id)
        if not (self.reserved <= b < self.num_blocks):
            raise AllocatorError(
                f"block id {b} out of pool range "
                f"[{self.reserved}, {self.num_blocks})")
        return b

    # -- prefix-cache bookkeeping -----------------------------------------

    def mark_cached(self, block_id: int) -> None:
        b = self._check_id(block_id)
        if b not in self._cached:
            self._cached.add(b)
            self._idle += self._ref[b] == 1

    def unmark_cached(self, block_id: int) -> None:
        b = self._check_id(block_id)
        if b in self._cached:
            self._cached.discard(b)
            self._idle -= self._ref[b] == 1

    # -- introspection / invariants ---------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def idle_cached_blocks(self) -> int:
        """Cached blocks no slot holds: what eviction could free."""
        return self._idle

    @property
    def used_blocks(self) -> int:
        """Blocks a live slot holds a ref on (``stats()["used"]``, kept as a
        running count)."""
        return (self.num_blocks - self.reserved - len(self._free)
                - self._idle)

    def stats(self) -> dict:
        cached_idle = sum(1 for b in self._cached if self._ref[b] == 1)
        used = sum(1 for b in range(self.reserved, self.num_blocks)
                   if self._ref[b] > 0) - cached_idle
        return {
            "total": self.num_blocks,
            "reserved": self.reserved,
            "free": len(self._free),
            "used": used,
            "cached": cached_idle,
            "allocs": self.allocs,
            "frees": self.frees,
            "alloc_failures": self.alloc_failures,
        }

    def check(self) -> List[str]:
        """Invariant audit; a non-empty return means the pool is corrupt
        (the engine flips unhealthy on it)."""
        out = []
        neg = [b for b, r in enumerate(self._ref) if r < 0]
        if neg:
            out.append(f"negative refcounts on blocks {neg}")
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            out.append("duplicate entries on the free list")
        both = [b for b in free_set if self._ref[b] != 0]
        if both:
            out.append(f"blocks {both} free-listed with nonzero refcount")
        s = self.stats()
        if s["free"] + s["used"] + s["cached"] != s["total"] - s["reserved"]:
            out.append(
                f"accounting leak: free({s['free']}) + used({s['used']}) "
                f"+ cached({s['cached']}) != total({s['total']}) - "
                f"reserved({s['reserved']})")
        uncached_idle = [b for b in self._cached if self._ref[b] == 0]
        if uncached_idle:
            out.append(f"cached blocks {uncached_idle} with refcount 0")
        if s["cached"] != self._idle:
            out.append(f"idle cached blocks counted {self._idle}, "
                       f"found {s['cached']}")
        return out


class PagedKVCache:
    """The engine's cache: block-pool KV storage with the traced-state
    surface of the dense :class:`KVCache`.

    Device state (threaded through compiled programs as lifted state): the
    K/V pools — ``k[layer]`` / ``v[layer]``, one
    ``[num_blocks, block_size, kv_heads, lane_dim]`` buffer each — the
    ``[slots, max_blocks_per_slot]`` int32 block tables, and the
    ``[slots]`` lengths.  Host state: the :class:`BlockAllocator` and each
    slot's owned-block list.
    """

    def __init__(self, num_slots: int, num_layers: int, max_seq: int,
                 num_kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, dtype="float32", *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 kernel: str = "reference",
                 sides: Optional[Sequence[Tuple[int, int]]] = None,
                 window: int = 0):
        if sides is None:
            if num_kv_heads is None or head_dim is None:
                raise ValueError("give num_kv_heads and head_dim, or sides")
            sides = ((num_kv_heads, head_dim),) * 2
        # (no layer: a pool that only counts a sequence's positions in
        # blocks, beside groups that keep state and no token)
        if num_slots < 1 or num_layers < 0 or max_seq < 1:
            raise ValueError("num_slots/max_seq must be >= 1, num_layers "
                             ">= 0")
        if kernel not in ("reference", "pallas"):
            raise ValueError(f"kernel must be 'reference' or 'pallas', "
                             f"got {kernel!r}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_seq % block_size != 0:
            raise ValueError(f"max_seq={max_seq} must be a multiple of "
                             f"block_size={block_size}")
        self.num_slots = int(num_slots)
        self.num_layers = int(num_layers)
        self.max_seq = int(max_seq)
        #: ``(heads, width)`` of each buffer a layer keeps, as the model
        #: stated them (``CacheSpec.sides``): K and V per KV head, or the
        #: one latent vector
        self.side_shapes = tuple((int(h), int(w)) for h, w in sides)
        self.num_kv_heads, self.head_dim = self.side_shapes[0]
        self.block_size = int(block_size)
        self.max_blocks_per_slot = self.max_seq // self.block_size
        #: retention (``window=``; not a subclass's window of another kind):
        #: 0 keeps every token of a sequence; ``W`` keeps the keys
        #: a query at the sequence's next position can read, its own and the
        #: ``W - 1`` before it — the blocks wholly behind them are released
        #: (:meth:`release_behind`) and the attention calls mask by ``W``
        self.kv_window = int(window)
        if self.kv_window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if num_blocks is None:
            # every slot at max_seq + the reserved scratch block; the
            # prefix cache then *saves* blocks relative to this baseline
            num_blocks = self.num_slots * self.max_blocks_per_slot + 1
        self.num_blocks = int(num_blocks)
        #: attention path for decode + tail prefill: ``"pallas"`` streams
        #: pool blocks through the flash-decoding kernels (interpret mode
        #: off-TPU), ``"reference"`` keeps the jnp gather + masked-softmax
        #: oracle.  Selection changes no compiled *shape* — both paths
        #: hang off the same step signatures.
        self.kernel = kernel
        from ..ops.pallas import use_pallas

        self._interpret = not use_pallas()
        #: the mesh the pools are sharded over (set by
        #: ``ServingShard.place_cache``; None = unsharded): the Pallas
        #: kernels then run once per head shard of it
        self.mesh = None
        self.dtype = dtype_mod.convert_dtype(dtype)
        self.allocator = BlockAllocator(self.num_blocks, reserved=1)
        #: the buffers' minor dim: the side's width in whole :data:`LANES`
        self.lane_dim = -(-self.head_dim // LANES) * LANES
        #: ``sides[s][layer]``: one ``[num_blocks, block_size, heads,
        #: lane_dim]`` buffer per side and layer
        self.sides = [
            [Tensor._wrap(jnp.zeros(
                (self.num_blocks, self.block_size, h, -(-w // LANES) * LANES),
                dtype=self.dtype)) for _ in range(self.num_layers)]
            for h, w in self.side_shapes]
        if len(self.sides) == 2:            # the K/V pool's own names
            self.k, self.v = self.sides
        self.block_tables = Tensor._wrap(jnp.full(
            (self.num_slots, self.max_blocks_per_slot), SCRATCH_BLOCK,
            dtype=jnp.int32))
        self.lengths = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        for t in (*self.buffers(), self.block_tables, self.lengths):
            t.persistable = True
        #: blocks each slot owns one ref on, by table index order
        self._slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
        #: leading entries of each slot's list released behind its window
        #: (the scratch block stands in their place)
        self._released = [0] * self.num_slots
        self.blocks_released = 0
        self.copy_on_extends = 0

    def buffers(self) -> List[Tensor]:
        """Every layer buffer of every side."""
        return [buf for side in self.sides for buf in side]

    # -- host-side slot lifecycle -----------------------------------------

    def _set_table(self, slot: int, idx: int, block_id: int) -> None:
        self.block_tables._set_data(
            self.block_tables._value().at[slot, idx].set(
                jnp.int32(block_id)))

    def warm_host_programs(self) -> None:
        """Runs, on the scratch block and an empty slot's table entry (both
        left as they were), the small eager programs the host-side block
        maintenance uses between steps — the table entry's scatter of
        ``ensure_capacity`` and the block copy of copy-on-extend — so that
        the first sequence to grow past its reserved blocks inside a
        measured window compiles nothing."""
        self._set_table(0, 0, int(self.block_tables.numpy()[0, 0]))
        for buf in {tuple(b.shape): b for b in self.buffers()}.values():
            arr = buf._value()
            buf._set_data(arr.at[SCRATCH_BLOCK].set(arr[SCRATCH_BLOCK]))

    def begin_sequence(self, slot: int, shared_blocks: Sequence[int],
                       prefix_len: int, tail_bucket: int, *,
                       total: int = 0, reserve: int = 0,
                       write: bool = True) -> bool:
        """Assign storage for one admission: ref the shared prefix blocks
        and allocate fresh blocks covering the whole tail bucket.  The
        slot must be empty (freshly popped).  All-or-nothing: returns
        False (slot untouched) when the pool cannot supply the tail —
        the scheduler defers the request instead of failing it.  Of a pool
        with a window the hit's leading entries may be the scratch block
        (blocks behind the window, which no hit needs).  ``reserve``: blocks
        that must still be obtainable afterwards, beside what this sequence
        of ``total`` tokens may itself grow by (:meth:`growth_need`): what
        the running sequences may still take.  ``write=False`` (here and in
        :meth:`extend_tail`, :meth:`release_behind`): the host's lists
        change and the device's table is left to the caller, who writes the
        slot's :meth:`table_rows` with whatever else it stages (the engine's
        one program an admission)."""
        if self._slot_blocks[slot]:
            raise AllocatorError(f"slot {slot} already owns blocks "
                                 f"{self._slot_blocks[slot]}")
        bs = self.block_size
        if prefix_len != len(shared_blocks) * bs:
            raise ValueError(f"prefix_len {prefix_len} != "
                             f"{len(shared_blocks)} shared blocks * {bs}")
        if tail_bucket % bs != 0:
            raise ValueError(f"tail bucket {tail_bucket} not a multiple "
                             f"of block_size {bs}")
        n_tail = tail_bucket // bs
        n_total = len(shared_blocks) + n_tail
        if n_total > self.max_blocks_per_slot:
            raise ValueError(
                f"prefix {len(shared_blocks)} + tail {n_tail} blocks "
                f"exceed max_blocks_per_slot {self.max_blocks_per_slot}")
        # ref the hit blocks BEFORE allocating the tail: alloc() may evict
        # idle cached blocks under pressure, and an un-ref'd hit block is
        # exactly that — pinning first makes the lookup result immune to
        # being recycled into this same sequence's tail
        owned = [int(b) for b in shared_blocks]
        for b in owned:
            if b != SCRATCH_BLOCK:
                self.allocator.ref(b)
        fresh = self.allocator.alloc(n_tail)
        if fresh is not None:
            self._slot_blocks[slot] = owned + fresh
            if reserve and self.available_blocks() < reserve \
                    + self.growth_need(slot, total):
                self._slot_blocks[slot] = []
                for b in fresh:
                    self.allocator.unref(b)
                fresh = None
        if fresh is None:
            for b in owned:
                if b != SCRATCH_BLOCK:
                    self.allocator.unref(b)
            return False
        self._released[slot] = next(
            (i for i, b in enumerate(owned) if b != SCRATCH_BLOCK),
            len(owned))
        if write:
            self._set_row(slot, owned + fresh)
        return True

    @staticmethod
    def _row(ids: Sequence[int], width: int) -> np.ndarray:
        """A table row: ``ids``, then the scratch block."""
        row = np.full((width,), SCRATCH_BLOCK, dtype=np.int32)
        row[:len(ids)] = ids
        return row

    def _set_row(self, slot: int, ids: Sequence[int],
                 tables: Optional[Tensor] = None) -> None:
        """``slot``'s row of ``tables`` (default: the block tables): ``ids``,
        then the scratch block."""
        tables = self.block_tables if tables is None else tables
        tables._set_data(tables._value().at[slot].set(
            self._row(ids, int(tables.shape[1]))))

    def tables(self) -> List[Tensor]:
        """The ``[slots, ...]`` int32 tables a slot has a row of, in the
        order of :meth:`table_rows`."""
        return [self.block_tables]

    def table_rows(self, slot: int) -> List[np.ndarray]:
        """``slot``'s row of every table as the host's lists have it now."""
        return [self._row(self._slot_blocks[slot], self.max_blocks_per_slot)]

    def available_blocks(self) -> int:
        """Blocks an allocation could get: free, or idle in the prefix
        cache."""
        return self.allocator.free_blocks + self.allocator.idle_cached_blocks

    def usable_blocks(self) -> int:
        """Blocks one admission's tail bucket may take at most."""
        return self.num_blocks - self.allocator.reserved \
            - (-(-self.kv_window // self.block_size) if self.kv_window else 0)

    def growth_need(self, slot: int, total: int) -> int:
        """Blocks ``slot``'s sequence may still take beyond those it holds,
        if it grows to ``total`` tokens: the rest of its length — or, of a
        pool with a window, of a window and the block being written, since a
        block behind the window goes before the next one is taken."""
        owned = self._slot_blocks[slot]
        want = -(-int(total) // self.block_size)
        if not self.kv_window:
            return max(0, want - len(owned))
        most = -(-self.kv_window // self.block_size) + 1
        return max(0, min(want, most) - (len(owned) - self._released[slot]))

    def extend_tail(self, slot: int, start: int, tail_bucket: int, *,
                    write: bool = True) -> bool:
        """Fresh blocks for the positions ``[start, start + tail_bucket)``
        that ``slot`` does not own yet: the next piece of a prompt that is
        prefilled in pieces.  False (nothing taken) when the pool cannot
        supply them."""
        owned = self._slot_blocks[slot]
        n = (start + tail_bucket) // self.block_size - len(owned)
        if n > 0:
            fresh = self.allocator.alloc(n)
            if fresh is None:
                return False
            owned.extend(fresh)
            if write:
                self._set_row(slot, owned)
        return True

    def release_behind(self, slot: int, next_pos: int, *,
                       write: bool = True) -> int:
        """Of a pool with a window: unreference ``slot``'s blocks that no
        live position can read — every key in them lies more than ``window -
        1`` positions behind ``next_pos``, the sequence's next query.  The
        scratch block takes their place in the list and the table.  Returns
        the blocks let go of (0 for a pool that keeps every token)."""
        if not self.kv_window:
            return 0
        owned = self._slot_blocks[slot]
        lo = self._released[slot]
        hi = min(max(0, int(next_pos) - self.kv_window + 1) // self.block_size,
                 len(owned))
        if hi <= lo:
            return 0
        drop = owned[lo:hi]
        owned[lo:hi] = [SCRATCH_BLOCK] * (hi - lo)
        self._released[slot] = hi
        if write:
            self._set_row(slot, owned)
        for b in drop:
            self.allocator.unref(b)
        self.blocks_released += len(drop)
        return len(drop)

    def release_slot(self, slot: int) -> None:
        """Drop the slot's refs and point its table back at scratch.
        Idempotent (retire is the single exit path, but chaos paths may
        race a reset)."""
        owned, self._slot_blocks[slot] = self._slot_blocks[slot], []
        self._released[slot] = 0
        for b in owned:
            if b != SCRATCH_BLOCK:      # released behind a window
                self.allocator.unref(b)
        if owned:
            self._set_row(slot, [])
        self.lengths._set_data(
            self.lengths._value().at[slot].set(jnp.int32(0)))

    def ensure_capacity(self, slot: int, next_pos: int) -> bool:
        """Make position ``next_pos`` writable for ``slot`` before a
        decode step: allocate the covering block if the sequence is
        growing into one it doesn't own yet, and copy-on-extend if the
        covering block is shared (refcount > 1).  Returns False when the
        pool is exhausted (the engine fails that request, not the
        engine)."""
        bidx = next_pos // self.block_size
        if bidx >= self.max_blocks_per_slot:
            return False                 # capacity guard upstream
        owned = self._slot_blocks[slot]
        if bidx >= len(owned):
            if bidx != len(owned):
                raise AllocatorError(
                    f"slot {slot} skipping block index {len(owned)} "
                    f"to {bidx}")
            fresh = self.allocator.alloc(1)
            if fresh is None:
                return False
            owned.append(fresh[0])
            self._set_table(slot, bidx, fresh[0])
            return True
        block_id = owned[bidx]
        if self.allocator.refcount(block_id) > 1:
            # copy-on-extend: appending into a shared block would corrupt
            # the other holders' view — give this slot a private copy
            fresh = self.allocator.alloc(1)
            if fresh is None:
                return False
            for buf in self.buffers():
                arr = buf._value()
                buf._set_data(arr.at[fresh[0]].set(arr[block_id]))
            owned[bidx] = fresh[0]
            self._set_table(slot, bidx, fresh[0])
            self.allocator.unref(block_id)
            self.copy_on_extends += 1
        return True

    def truncate_blocks(self, slot: int, n_tokens: int) -> int:
        """Speculative rollback bookkeeping: drop the slot's owned
        blocks past the ones covering positions ``0..n_tokens-1`` (the
        rejected tail of a verify window — no copy, just refcount +
        table writes; the rejected K/V bytes become unreadable the
        moment the in-graph length rollback lands).  Returns how many
        blocks were released."""
        owned = self._slot_blocks[slot]
        keep = (int(n_tokens) + self.block_size - 1) // self.block_size
        if len(owned) <= keep:
            return 0
        drop = owned[keep:]
        del owned[keep:]
        self._set_row(slot, owned)
        for b in drop:
            self.allocator.unref(b)
        return len(drop)

    def reset(self) -> None:
        """Forget all sequences: release every slot and zero lengths.
        Cached (prefix) blocks are left to their owner — the engine
        clears its PrefixCache separately when it wants a cold pool."""
        for slot in range(self.num_slots):
            self.release_slot(slot)

    # -- traced state ops (CacheContext surface) --------------------------

    def _to_lanes(self, upd, dtype, lanes: Optional[int] = None):
        """New entries ``[..., heads, width]`` in the pool's dtype and lane
        width (``lanes``: a side's own, where the sides differ)."""
        lanes = self.lane_dim if lanes is None else lanes
        pad = [(0, 0)] * (upd.ndim - 1) + [(0, lanes - upd.shape[-1])]
        return jnp.pad(upd.astype(dtype), pad)

    def _layer(self, layer_idx: int) -> List[Tensor]:
        return [side[layer_idx] for side in self.sides]

    def gather(self, pool_layer, block_tables):
        """Reference read: the tables' blocks of one layer's buffer as
        contiguous ``[B, max_blocks * block_size, Hkv, D]`` sequences."""
        return gather_block_kv(pool_layer, block_tables)[..., :self.head_dim]

    def prefill_write(self, layer_idx: int, slot, k, v, start=0) -> None:
        """Write a tail bucket's K/V through the block table.

        ``k``/``v``: ``[1, S, Hkv, D]`` with S = tail bucket (a multiple
        of block_size); ``slot``/``start`` scalar ints (may be traced) —
        ``start`` is the absolute position of the bucket's first token
        and is always a block boundary.  The bucket's blocks (distinct,
        freshly allocated by ``begin_sequence``) are written by ONE
        scatter of whole blocks per pool: a per-block update loop made
        the 1024-token bucket of a 24-layer model a 3,000-op program that
        took minutes to trace and compile."""
        self._prefill_write(layer_idx, slot, (k, v), start)

    def _prefill_write(self, layer_idx: int, slot, news, start) -> None:
        """``prefill_write`` of one new tensor per side."""
        s = _as_i32(slot).reshape(())
        st = _as_i32(start).reshape(())
        bs = self.block_size
        n_blocks = int(news[0].shape[1]) // bs
        tbl = self.block_tables._value()
        row = jax.lax.dynamic_index_in_dim(tbl, s, axis=0, keepdims=False)
        block_ids = jax.lax.dynamic_slice_in_dim(row, st // bs, n_blocks)
        for buf, new in zip(self._layer(layer_idx), news):
            arr = buf._value()
            upd = self._to_lanes(new._value()[0], arr.dtype,
                                 arr.shape[-1])             # [S, Hkv, Dp]
            upd = upd.reshape(n_blocks, bs, *upd.shape[1:])
            with jax.named_scope(KV_WRITE_SCOPE):
                if self.kernel == "pallas" and blocks_need_kernel_write(
                        arr.shape[2], arr.dtype.itemsize):
                    buf._set_data(write_blocks(arr, upd, block_ids,
                                               interpret=self._interpret))
                else:
                    buf._set_data(arr.at[block_ids].set(upd))

    def set_length(self, slot, length) -> None:
        s = _as_i32(slot).reshape(())
        ln = _as_i32(length).reshape(())
        self.lengths._set_data(self.lengths._value().at[s].set(ln))

    def _decode_token_write(self, layer_idx: int, *news):
        """Write one token per slot at ``lengths[slot]`` through the
        table, one new tensor per side.  Idle slots' tables point at the
        scratch block, so the fixed-shape all-slots write never lands on
        live storage.  Returns ``(*layer buffers, tables, lengths)`` raw
        arrays (post-write layer pools)."""
        lens = self.lengths._value()
        bs = self.block_size
        tbl = self.block_tables._value()            # [slots, max_blocks]
        bidx = jnp.clip(lens // bs, 0, self.max_blocks_per_slot - 1)
        block_ids = jnp.take_along_axis(
            tbl, bidx[:, None], axis=1)[:, 0]       # [slots]
        off = lens % bs
        layers = []
        for buf, new in zip(self._layer(layer_idx), news):
            arr = buf._value()
            upd = self._to_lanes(new._value()[:, 0], arr.dtype,
                                 arr.shape[-1])
            with jax.named_scope(KV_WRITE_SCOPE):
                arr = arr.at[block_ids, off].set(upd)
            buf._set_data(arr)
            layers.append(arr)
        return (*layers, tbl, lens)

    # -- the latent pool's calls (one side, one "head") ---------------------

    def latent_prefill_write(self, layer_idx: int, slot, lat, start) -> None:
        """``prefill_write`` of the one latent vector a token:
        ``lat [1, S, width]``."""
        self._prefill_write(layer_idx, slot,
                            (Tensor._wrap(lat._value()[:, :, None, :]),),
                            start)

    def _latent_pool(self, layer_idx: int):
        """This layer's pool as the latent kernels take it: ``[num_blocks,
        block_size, lanes]``, the one "head" dropped."""
        pool = self.sides[0][layer_idx]._value()
        return pool.reshape(pool.shape[0], pool.shape[1], pool.shape[3])

    def latent_decode_attention(self, layer_idx: int, q_lat, lat, active, *,
                                scale: float, dv: int):
        """One decode step of absorbed latent attention for this layer:
        write each slot's vector ``lat [slots, 1, width]``, then
        ``q_lat [slots, 1, H, width]`` (zero-padded to the pool's lanes
        here) attends over each active slot's window: ``mla_paged_decode``,
        or its jnp oracle under ``kernel="reference"``.  Returns
        ``[slots, 1, H, dv]``."""
        from ..ops.pallas import mla_attention_kernel as mla

        _pool, tbl, lens = self._decode_token_write(
            layer_idx, Tensor._wrap(lat._value()[:, :, None, :]))
        q = q_lat._value()[:, 0]
        args = (self._to_lanes(q, q.dtype), self._latent_pool(layer_idx), tbl,
                lens, _as_i32(active))
        if self.kernel == "pallas":
            out = mla.mla_paged_decode(*args, scale=scale, dv=dv,
                                       interpret=self._interpret)
        else:
            out = mla.mla_decode_reference(*args, scale=scale, dv=dv)
        return Tensor._wrap(out[:, None])

    def latent_prefill_attention(self, layer_idx: int, slot, q, lat, w_uk,
                                 w_uv, start, length, *, scale: float):
        """Tail queries ``q [1, S, H, nope + rope]`` over the tail's own
        latents ``lat [1, S, width]`` (just written; attended to in the
        up-projected form, from the program's hands) and the ``start``
        cached tokens of the slot's block row (absorbed, off the pool):
        ``mla_prefill``, or its one-softmax oracle under
        ``kernel="reference"``.  Returns ``[1, S, H, v]``, through ``W^V``."""
        from ..ops.pallas import mla_attention_kernel as mla

        row = jax.lax.dynamic_index_in_dim(
            self.block_tables._value(), _as_i32(slot).reshape(()), axis=0,
            keepdims=False)
        args = (self._latent_pool(layer_idx), row, _as_i32(start).reshape(()),
                _as_i32(length).reshape(()))
        if self.kernel == "pallas":
            out = mla.mla_prefill(q._value()[0], lat._value()[0], w_uk, w_uv,
                                  *args, scale=scale,
                                  interpret=self._interpret)
        else:
            out = mla.mla_prefill_oracle(q._value()[0], w_uk, w_uv, *args,
                                         scale=scale)
        return Tensor._wrap(out[None])

    # -- the indexed pool's calls (K, V and the indexer's key) ---------------

    def indexed_prefill_write(self, layer_idx: int, slot, k, v, k_idx,
                              start) -> None:
        """``prefill_write`` of the three sides: ``k``/``v [1, S, Hkv, D]``
        and the indexer's key ``k_idx [1, S, Di]``."""
        self._prefill_write(
            layer_idx, slot,
            (k, v, Tensor._wrap(k_idx._value()[:, :, None, :])), start)

    def _index_operands(self, layer_idx: int, q_idx, w):
        """This layer's indexer pool as the kernels take it (``[num_blocks,
        block_size, lanes]``: the one "head" dropped) and ``q_idx [..., Hi,
        Di]`` zero-padded to its lanes, ``w`` float32."""
        pool = self.sides[2][layer_idx]._value()
        pool = pool.reshape(pool.shape[0], pool.shape[1], pool.shape[3])
        return (pool, self._to_lanes(q_idx, q_idx.dtype, pool.shape[-1]),
                w.astype(jnp.float32))

    def indexed_decode_attention(self, layer_idx: int, q, k, v, q_idx, k_idx,
                                 w, active, *, topk: int):
        """One decode step of attention under the indexer for this layer:
        write each slot's K, V and indexer key, then attend.  While every
        running slot's context is ``topk`` tokens or fewer the selection is
        everything and the step is :meth:`decode_attention`'s dense read;
        otherwise each slot's query scores its cached indexer keys, keeps
        the ``topk`` best, and attends over those rows only.  Returns
        ``(out [slots, 1, H, D], selected, context)``: the tokens the
        running slots attended to and had cached (int32 scalars)."""
        from ..ops.pallas import dsa_attention_kernel as dsa

        k_l, v_l, _i_l, tbl, lens = self._decode_token_write(
            layer_idx, k, v, Tensor._wrap(k_idx._value()[:, :, None, :]))
        act = _as_i32(active)
        context = jnp.sum(jnp.where(act > 0, lens + 1, 0))
        D = q.shape[3]

        def dense():
            return self._dense_decode_read(q, k_l, v_l, tbl, lens,
                                           act)._value(), context

        def indexed():
            pool, qi, wi = self._index_operands(
                layer_idx, q_idx._value()[:, 0], w._value()[:, 0])
            out, n = dsa.indexed_decode(
                self._to_lanes(q._value()[:, 0], q.dtype, k_l.shape[-1]), qi,
                wi, k_l, v_l, pool, tbl, lens, act, topk=topk,
                heads=q_idx.shape[2], scale=D ** -0.5, kernel=self.kernel,
                interpret=self._interpret)
            return (out[:, None, :, :D].astype(q.dtype),
                    jnp.sum(jnp.where(act > 0, n, 0)))

        longest = jnp.max(jnp.where(act > 0, lens, 0))
        out, selected = jax.lax.cond(longest < topk, dense, indexed)
        return Tensor._wrap(out), selected, context

    def indexed_prefill_attention(self, layer_idx: int, slot, q, q_idx, w,
                                  start, length, *, topk: int):
        """Tail queries ``q [1, S, H, D]`` over the slot's whole block row
        under the indexer.  A prompt of ``topk`` tokens or fewer selects
        everything: the dense :meth:`PagedCacheContext.prefill_attention`
        read; a longer one scores, cuts and attends under causal AND
        selected.  Returns ``[1, S, H, D]``."""
        from ..ops.pallas import dsa_attention_kernel as dsa

        st = _as_i32(start).reshape(())
        ln = _as_i32(length).reshape(())
        k_l, v_l = (self.sides[i][layer_idx] for i in (0, 1))
        row = jax.lax.dynamic_index_in_dim(
            self.block_tables._value(), _as_i32(slot).reshape(()), axis=0,
            keepdims=False)
        D = q.shape[3]

        def dense():
            return self.dense_prefill_attention(
                layer_idx, slot, q, Tensor._wrap(st),
                Tensor._wrap(ln))._value()

        def indexed():
            pool, qi, wi = self._index_operands(
                layer_idx, q_idx._value()[0], w._value()[0])
            out = dsa.indexed_prefill(
                self._to_lanes(q._value()[0], q.dtype, k_l.shape[-1]), qi, wi,
                k_l._value(), v_l._value(), pool, row, st, ln, topk=topk,
                heads=q_idx.shape[2], scale=D ** -0.5, kernel=self.kernel,
                interpret=self._interpret)
            return out[None, :, :, :D].astype(q.dtype)

        return Tensor._wrap(jax.lax.cond(ln <= topk, dense, indexed))

    def dense_prefill_attention(self, layer_idx: int, slot, q, start,
                                length=None):
        """Tail queries ``q [1, S, H, D]`` at ``start ..`` over the slot's
        whole block row (cached prefix + freshly-written tail) of this
        layer's K and V under the absolute-position causal mask.
        ``kernel="pallas"`` reads the block row through the fused
        prefix+tail kernel instead of gathering a contiguous copy first; told
        the prompt's real ``length``, it visits no query tile of padding and
        returns zeros there (the reference computes the pad rows)."""
        k_layer, v_layer = (self.sides[i][layer_idx] for i in (0, 1))
        tbl = self.block_tables._value()
        s = _as_i32(slot).reshape(())
        if self.kernel == "pallas":
            row = jax.lax.dynamic_index_in_dim(tbl, s, axis=0,
                                               keepdims=False)      # [MB]
            return paged_prefill_attention(
                q, k_layer, v_layer, Tensor._wrap(row), start,
                interpret=self._interpret, mesh=self.mesh,
                window=self.kv_window, length=length)
        row = jax.lax.dynamic_index_in_dim(tbl, s, axis=0)           # [1, MB]
        return block_prefill_attention(
            q, Tensor._wrap(self.gather(k_layer._value(), row)),
            Tensor._wrap(self.gather(v_layer._value(), row)), start,
            window=self.kv_window)

    def decode_attention(self, layer_idx: int, q, k, v, active):
        """One decode step of attention for this layer: write the token,
        then attend (:meth:`_dense_decode_read`)."""
        k_layer, v_layer, tbl, lens = self._decode_token_write(
            layer_idx, k, v)
        return self._dense_decode_read(q, k_layer, v_layer, tbl, lens,
                                       _as_i32(active))

    def _dense_decode_read(self, q, k_layer, v_layer, tbl, lens, active):
        """Attention of ``q [slots, 1, H, D]`` over each slot's whole window
        in the post-write layer buffers.  ``kernel="pallas"`` consumes the
        block table inside the flash-decoding kernel (no materialized
        contiguous K/V), which visits the ``active`` slots only and returns
        zero rows for the others; ``"reference"`` gathers and runs the jnp
        oracle over every slot — identical on the active rows, asserted in
        tests/test_paged_kernel.py."""
        if self.kernel == "pallas":
            return paged_decode_attention(
                q, Tensor._wrap(k_layer), Tensor._wrap(v_layer),
                Tensor._wrap(tbl), Tensor._wrap(lens), Tensor._wrap(active),
                interpret=self._interpret, mesh=self.mesh,
                window=self.kv_window)
        return cached_attention(
            q, Tensor._wrap(self.gather(k_layer, tbl)),
            Tensor._wrap(self.gather(v_layer, tbl)), Tensor._wrap(lens),
            window=self.kv_window)

    def decode_chunk_tokens(self) -> Optional[int]:
        """Tokens one work item of this pool's Pallas decode kernel attends
        over, from the shapes a shard of the pool has (the kernels' own
        functions); None under ``kernel="reference"``, which has no work
        list.  A running slot with ``n`` cached tokens costs a decode step
        ``n // tokens + 1`` items a layer: the engine's ``decode_chunks``."""
        if self.kernel != "pallas":
            return None
        arr = self.sides[0][0]._value()
        _, bs, heads, lanes = arr.sharding.shard_shape(arr.shape)
        if len(self.sides) == 1:
            from ..ops.pallas.mla_attention_kernel import chunk_tokens

            return chunk_tokens(bs, self.max_blocks_per_slot)
        from ..ops.pallas.paged_attention_kernel import decode_chunk_tokens

        return decode_chunk_tokens(bs, self.max_blocks_per_slot, heads, lanes,
                                   arr.dtype.itemsize)

    def verify_write(self, layer_idx: int, k, v):
        """Speculative verify write through the block table: W tokens
        per slot at positions ``lengths[slot] .. lengths[slot]+W-1``.
        Block ids stay tensor VALUES (one executable for every table
        content); positions past ``max_seq`` are redirected to the
        scratch block, so a near-capacity slot's over-the-end window
        writes land on storage nothing ever reads.  The caller must
        have pre-extended each running slot's table to cover the
        in-range window (``ensure_capacity`` per position — exclusive
        ownership via copy-on-extend included).  Returns
        ``(k_layer, v_layer, tables, lengths)`` raw arrays."""
        lens = self.lengths._value()
        bs = self.block_size
        tbl = self.block_tables._value()            # [slots, max_blocks]
        W = int(k.shape[1])
        pos = lens[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        bidx = jnp.clip(pos // bs, 0, self.max_blocks_per_slot - 1)
        block_ids = jnp.take_along_axis(tbl, bidx, axis=1)   # [slots, W]
        block_ids = jnp.where(pos < self.max_seq, block_ids,
                              SCRATCH_BLOCK)
        off = pos % bs
        layers = []
        for buf, new in zip(self._layer(layer_idx), (k, v)):
            arr = buf._value()
            upd = self._to_lanes(new._value(), arr.dtype)  # [slots,W,Hkv,Dp]
            with jax.named_scope(KV_WRITE_SCOPE):
                arr = arr.at[block_ids, off].set(upd)
            buf._set_data(arr)
            layers.append(arr)
        return layers[0], layers[1], tbl, lens

    def verify_attention(self, layer_idx: int, q, k, v):
        """One verify-window step for this layer: write the W-token
        window through the table, gather the slot sequences contiguous,
        and attend with the per-slot offset causal mask.  The verify
        path always uses the XLA gather + ``ops.verify_attention``
        oracle (the Pallas decode/prefill kernels are W-specific and
        stay on their own paths) — semantics identical either way, and
        kernel selection still never changes a compiled shape."""
        k_layer, v_layer, tbl, lens = self.verify_write(layer_idx, k, v)
        return verify_attention(
            q, Tensor._wrap(self.gather(k_layer, tbl)),
            Tensor._wrap(self.gather(v_layer, tbl)),
            Tensor._wrap(lens))

    def advance(self, active) -> None:
        mask = _as_i32(active)
        self.lengths._set_data(self.lengths._value() + mask)

    # -- host-side management ---------------------------------------------

    def owned_blocks(self, slot: int) -> List[int]:
        """The block ids ``slot`` holds a ref on, in table-index order —
        the engine's handle for prefix-cache registration (at admission,
        and again on preemption BEFORE the victim's slot releases, so a
        preempted request's resume is a cheap prefix hit)."""
        return self._slot_blocks[slot]

    def shorten_hit(self, shared: List[int]) -> Tuple[int, List[int]]:
        """A prefix hit less its last block: ``(tokens, blocks)``."""
        return (len(shared) - 1) * self.block_size, shared[:-1]

    def decode_items_fn(self) -> Optional[Callable[[int], int]]:
        """``f(seq_len)``: work items a layer a running slot with ``seq_len``
        cached tokens costs a decode step (None: no work list).  Made once
        with the programs: the engine calls it for every running slot of
        every step."""
        ct = self.decode_chunk_tokens()
        if ct is None:
            return None
        w = self.kv_window
        if not w:
            return lambda seq_len: seq_len // ct + 1
        return lambda seq_len: seq_len // ct - max(0, seq_len - w + 1) // ct \
            + 1

    def prefill_work(self, slot: int, bucket: int, start: int, length: int,
                     query_heads: int, *, indexed: bool = False
                     ) -> Optional[Tuple[int, int, int]]:
        """``(items, tile_rows, run_items)``: what one layer of this pool
        costs the Pallas tail-prefill kernel for ``slot``'s tail ``[start,
        length)`` in a ``bucket``-row program — the (query tile, key chunk)
        work items it walks, the query rows it multiplies (whole tiles), and
        the items whose chunk comes in one copy a side (a run of the pool:
        from the block ids the allocator holds for the slot, no pull from the
        device) — by the kernel's own plan, list and rule, on the host.
        ``indexed``: the tail takes the kernel under the indexer's selection
        (a prompt past ``topk``; its tile is the same for the bucket as for
        the ``PREFILL_SCORE_ROWS`` queries of a call).
        None under ``kernel="reference"``, which has no work list."""
        if self.kernel != "pallas":
            return None
        from ..ops.pallas import dsa_attention_kernel as dsa
        from ..ops.pallas import paged_attention_kernel as pk

        arr = self.sides[0][0]._value()
        _, bs, heads, lanes = arr.sharding.shard_shape(arr.shape)
        mb = self.max_blocks_per_slot
        if indexed:
            ts, ct = dsa.sparse_prefill_plan(bucket, bs, mb)
        else:
            ts, ct = pk.prefill_plan(bucket, heads,
                                     query_heads // arr.shape[2], lanes,
                                     arr.dtype.itemsize, bs, mb)
        items, runs = pk.prefill_item_counts(
            self.table_rows(slot)[0], start, length, S=bucket, tile=ts,
            chunk_tokens=ct, block_size=bs, window=self.kv_window)
        return items, -(-(length - start) // ts) * ts, runs

    def layer_nbytes(self) -> int:
        """Bytes of one layer's buffer of the first side (K and V are
        alike), pad lanes included."""
        return int(self.sides[0][0]._value().nbytes)

    def nbytes(self) -> int:
        return sum(int(buf._value().nbytes) for buf in self.buffers())

    def blocks_in_use(self) -> int:
        s = self.allocator.stats()
        return s["used"] + s["cached"]

    def check_invariants(self) -> List[str]:
        """Allocator audit plus cache-level cross-checks."""
        out = self.allocator.check()
        seen = {}
        for slot, owned in enumerate(self._slot_blocks):
            for b in owned:
                if b == SCRATCH_BLOCK:          # released behind a window
                    continue
                seen.setdefault(b, []).append(slot)
                if self.allocator.refcount(b) < 1:
                    out.append(f"slot {slot} holds freed block {b}")
        for b, slots in seen.items():
            if len(slots) > self.allocator.refcount(b):
                out.append(f"block {b} held by slots {slots} with only "
                           f"{self.allocator.refcount(b)} refs")
        return out


@dataclass
class PagedCacheContext(CacheContext):
    """CacheContext over a :class:`PagedKVCache`: same duck surface, plus
    the tail-prefill routing (``start`` = absolute position of the
    bucket's first token, a traced scalar — block ids stay inside the
    block-table tensor)."""

    start: Optional[Tensor] = None              # prefill: scalar int32

    def _prefill_start(self):
        return _as_i32(self.start if self.start is not None else 0
                       ).reshape(())

    # -- the indexed pool: one write and two attention calls ----------------

    def write_prefill_indexed(self, k, v, k_idx) -> None:
        self.cache.indexed_prefill_write(self.layer_idx, self.slot, k, v,
                                         k_idx, self._prefill_start())

    def indexed_prefill_attention(self, q, q_idx, w, *, topk: int):
        return self.cache.indexed_prefill_attention(
            self.layer_idx, self.slot, q, q_idx, w, self._prefill_start(),
            self.length, topk=topk)

    def indexed_decode_attention(self, q, k, v, q_idx, k_idx, w, *,
                                 topk: int):
        if self.mode != "decode":
            raise ValueError("the indexed pool has no verify form")
        out, selected, context = self.cache.indexed_decode_attention(
            self.layer_idx, q, k, v, q_idx, k_idx, w, self.active, topk=topk)
        self.note_selection(selected, context)
        return out

    # -- the windowed pool: exact window + summaries, two attention calls ----

    def windowed_prefill_attention(self, q, k, v, phi, mu):
        return self.cache.windowed_prefill_attention(
            self.layer_idx, self.slot, q, k, v, phi, mu,
            self._prefill_start(), self.length)

    def windowed_decode_attention(self, q, k, v):
        if self.mode != "decode":
            raise ValueError("the windowed pool has no verify form")
        out, exact, summary, context = self.cache.windowed_decode_attention(
            self.layer_idx, q, k, v, self.active)
        self.note_rows(exact, summary, context)
        return out

    # -- the latent pool: one write and two attention calls -----------------

    def write_prefill_latent(self, lat) -> None:
        self.cache.latent_prefill_write(self.layer_idx, self.slot, lat,
                                        self._prefill_start())

    def latent_prefill_attention(self, q, lat, w_uk, w_uv, *, scale: float):
        return self.cache.latent_prefill_attention(
            self.layer_idx, self.slot, q, lat, w_uk, w_uv,
            self._prefill_start(), self.length, scale=scale)

    def latent_decode_attention(self, q_lat, lat, *, scale: float, dv: int):
        if self.mode != "decode":
            raise ValueError("the latent pool has no verify form")
        return self.cache.latent_decode_attention(
            self.layer_idx, q_lat, lat, self.active, scale=scale, dv=dv)

    # -- a state group: no token kept, one call ------------------------------

    def shift_state(self, z):
        """A state layer's columns ``z [B, S, width]`` (a raw array) through
        its group's buffer: the taps ``[z[t - rows], .., z[t]]`` of every
        position, ``rows + 1`` raw arrays ``[B, S, width]`` in the cache's
        dtype — a prefill's first columns come from the state its slot's plan
        names and its real end is written back; a decode step shifts the
        running slots' state by its one column."""
        if self.mode == "prefill":
            return self.cache.state_prefill(
                self.layer_idx, self.slot, z, self._prefill_start(),
                self.length)
        if self.mode != "decode":
            raise ValueError("a state group has no verify form")
        return self.cache.state_decode(self.layer_idx, z, self.active)

    def recurrent_start(self, seq_len: int):
        """A tail prefill of ``seq_len`` rows in a layer whose group keeps a
        recurrent side: ``(state, ends)`` — the state before the tail's first
        row (of the snapshot the slot's plan names; zeros for a cold prompt)
        and the tail-relative row counts the model returns the state after
        (:meth:`recurrent_finish`; 0: none).  Raw arrays."""
        if self.mode != "prefill":
            raise ValueError("a state group has no verify form")
        return self.cache.recurrent_start(self.layer_idx, self.slot, seq_len)

    def recurrent_finish(self, seq_len: int, last, kept) -> None:
        """The state at the tail's real end (the slot's from now on) and at
        :meth:`recurrent_start`'s ends (the planned snapshots)."""
        self.cache.recurrent_finish(self.layer_idx, self.slot, seq_len, last,
                                    kept)

    def recurrent_step(self, step):
        """A decode step of such a layer: ``step(state [slots, heads, d_k,
        d_v], active [slots]) -> (out, state)`` rewrites the running slots'
        state in place; returns ``out``."""
        if self.mode != "decode":
            raise ValueError("a state group has no verify form")
        return self.cache.recurrent_step(self.layer_idx, step, self.active)

    def write_prefill(self, k, v) -> None:
        self.cache.prefill_write(self.layer_idx, self.slot, k, v,
                                 self._prefill_start())

    def decode_attention(self, q, k, v):
        """The pool takes the whole decode step (token write + its
        kernel-vs-reference read); the verify window stays the base's."""
        if self.mode == "verify":
            return super().decode_attention(q, k, v)
        return self.cache.decode_attention(self.layer_idx, q, k, v,
                                           self.active)

    def prefill_positions(self, seq_len: int) -> Optional[Tensor]:
        """Absolute positions of the tail bucket's tokens ``[1, S]`` —
        offset by the cached-prefix length."""
        return Tensor._wrap((self._prefill_start() + jnp.arange(
            seq_len, dtype=jnp.int32))[None, :])

    def prefill_attention(self, q, k, v):
        """Tail queries attending over the slot's whole block table
        (cached prefix + freshly-written tail) with an absolute-position
        causal mask.  GQA expansion happens inside the op, like the
        decode kernel (:meth:`PagedKVCache.dense_prefill_attention`)."""
        return self.cache.dense_prefill_attention(
            self.layer_idx, self.slot, q,
            self.start if self.start is not None else 0, self.length)

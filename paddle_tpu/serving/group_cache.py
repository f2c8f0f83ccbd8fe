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
- **a state group** (``CacheGroup.state``, :class:`StatePool`) keeps nothing
  a token: one buffer of fixed size a slot a layer, which every token
  rewrites.  There is no block to share, so what a hit can reuse is a
  **snapshot** of the state that a tail prefill wrote into a small pool of
  rows at exactly the hit's end: a hit ends at the longest registered length
  where the groups above have their blocks *and* every state group has a
  snapshot, shortened otherwise.  The tail prefill reads its first state from
  the pool inside its own program (row 0: zeros, a cold prompt) and writes
  the slot's state from the prompt's real end.  Its sides are of two kinds
  (``CacheGroup``): a *shift* side the pool itself shifts, and a
  *recurrent* side — a float32 matrix a head — that the model's recurrence
  maps forward: the pool hands a prefill its starting state and the ends to
  return states at, takes back the state at the tail's real end and at
  those ends, and gives a decode step the layer's buffer to rewrite in
  place for the running slots.  One snapshot row, one plan and one
  reference count cover all of a group's sides.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtype_mod
from ..core.tensor import Tensor
from .kv_cache import CacheGroup, _as_i32
from .paging import BlockAllocator, PagedKVCache, SCRATCH_BLOCK
from .prefix_cache import ChainKeys, PrefixCache, as_tokens

__all__ = ["GroupedKVCache", "GroupedPrefixCache", "StatePool"]

#: a hit: block ids by absolute position, one list a group that keeps tokens
#: (the scratch block where a group with a window needs none), then the
#: snapshot row of each state group (0: none, the zeros)
GroupHit = Tuple[object, ...]

#: the snapshot row that is zeros and never written: a cold prompt's state
ZERO_ROW = 0
#: a tail prefill leaves a snapshot at every absolute position that is a
#: multiple of its group's stride (and at its prompt's last whole block);
#: this is the stride of a group that states none (``CacheGroup.stride`` 0)
SNAPSHOT_STRIDE = 256
#: the bytes a snapshot pool takes at most when its rows are not given
SNAPSHOT_POOL_BYTES = 1 << 30
#: named scope of a state group's writes (the slot's state, the snapshots)
STATE_WRITE_SCOPE = "state.write"


class StatePool:
    """A state group's storage, a side of each kind at most.

    The **shift** side: ``state [layers, rows, slots, width]``, the buffer
    each slot keeps a layer — the last ``rows`` columns of a ``width``-wide
    product — and ``snapshots [layers, rows, snapshot rows, width]``, copies
    of it that tail prefills left at known lengths.  Slots and snapshot rows
    lie on the tiled dims, so a decode step's shift is whole tiles.

    The **recurrent** side ``(heads, d_k, d_v)``, float32: ``recurrent[layer]
    [slots, heads, d_k, d_v]`` and ``recurrent_snapshots[layer] [snapshot
    rows, heads, d_k, d_v]``, a buffer a layer so that a decode kernel can
    alias the one it rewrites.  The pool never computes it: a prefill is
    given its starting state and returns the states to keep, a decode step
    is given the layer's buffer.

    One allocator of snapshot rows (row 0 is zeros and never written) and
    one **plan** a slot cover both: a row of int32 the host writes at
    admission (device state like a block table): the snapshot row the
    prefill program starts from (:data:`ZERO_ROW`: a cold prompt), then the
    rows to write and the tail-relative ends to write them at (a row past
    the pool: nothing is written)."""

    def __init__(self, num_slots: int, num_layers: int, sides, dtype, *,
                 block_size: int, max_tail: int,
                 num_snapshots: Optional[int] = None, stride: int = 0,
                 chunk: int = 0):
        self.num_slots, self.num_layers = int(num_slots), int(num_layers)
        sides = (tuple(sides),) if isinstance(sides[0], int) else tuple(sides)
        shift = [s for s in sides if len(s) == 2]
        recurrent = [s for s in sides if len(s) == 3]
        if len(shift) > 1 or len(recurrent) > 1 \
                or len(shift) + len(recurrent) != len(sides):
            raise ValueError(f"a state pool keeps a shift side (rows, width) "
                             f"and a recurrent side (heads, d_k, d_v) at "
                             f"most, got {sides}")
        self.rows, self.width = (int(n) for n in (shift or [(0, 0)])[0])
        #: ``(heads, d_k, d_v)`` of the recurrent side, or None
        self.recurrent_shape = tuple(int(n) for n in recurrent[0]) \
            if recurrent else None
        self.block_size = int(block_size)
        self.stride = int(stride) or SNAPSHOT_STRIDE
        if self.stride % self.block_size:
            raise ValueError(f"block_size {block_size} must divide the "
                             f"snapshot stride {self.stride}")
        #: rows the recurrent side's scan takes at a time (0: none)
        self.chunk = int(chunk)
        #: snapshots one prefill program writes at most: the stride's, and
        #: the one at the prompt's last whole block
        self.max_snaps = int(max_tail) // self.stride + 1
        self.dtype = dtype_mod.convert_dtype(dtype)
        if num_snapshots is None:
            # every slot's prefill and what it started from, as far as the
            # bytes allow: a row weighs what a slot's state weighs
            num_snapshots = max(2, min(
                self.num_slots * (self.max_snaps + 1) + 1,
                SNAPSHOT_POOL_BYTES // self.slot_nbytes()))
        self.num_blocks = int(num_snapshots)
        if self.num_blocks < 2:
            raise ValueError("a snapshot pool holds the zero row and one more "
                             f"at least, got {num_snapshots}")
        self.allocator = BlockAllocator(self.num_blocks, reserved=1)
        self.state = self.snapshots = None
        if shift:
            self.state = Tensor._wrap(jnp.zeros(
                (self.num_layers, self.rows, self.num_slots, self.width),
                self.dtype))
            self.snapshots = Tensor._wrap(jnp.zeros(
                (self.num_layers, self.rows, self.num_blocks, self.width),
                self.dtype))
        self.recurrent: List[Tensor] = []
        self.recurrent_snapshots: List[Tensor] = []
        if recurrent:
            for _ in range(self.num_layers):
                self.recurrent.append(Tensor._wrap(jnp.zeros(
                    (self.num_slots, *self.recurrent_shape), jnp.float32)))
                self.recurrent_snapshots.append(Tensor._wrap(jnp.zeros(
                    (self.num_blocks, *self.recurrent_shape), jnp.float32)))
        #: the plan rows as the host wrote them last (the device's copy is
        #: ``plan``)
        self._plans = np.tile(self._plan_row(ZERO_ROW, {}, 0),
                              (self.num_slots, 1))
        self.plan = Tensor._wrap(jnp.asarray(self._plans))
        for t in (*self.buffers(), self.plan):
            t.persistable = True
        #: snapshot rows each slot holds a reference on: the one it started
        #: from and the ones its prefill wrote
        self._held: List[List[int]] = [[] for _ in range(self.num_slots)]
        #: ``{absolute length: row}`` of what each slot's prefill wrote
        self._wrote: List[Dict[int, int]] = [{} for _ in range(self.num_slots)]
        self.snapshots_written = 0
        #: snapshots a prefill went without because no row was free
        self.snapshots_skipped = 0
        #: admissions by what their first program started from
        self.restored, self.cold = 0, 0

    # -- host-side slot lifecycle ---------------------------------------------

    def buffers(self) -> List[Tensor]:
        shift = [self.state, self.snapshots] if self.state is not None else []
        return shift + self.recurrent + self.recurrent_snapshots

    def nbytes(self) -> int:
        return sum(int(b._value().nbytes) for b in self.buffers())

    def recurrent_nbytes(self) -> int:
        """Bytes of one slot's recurrent state in one layer."""
        return 4 * int(np.prod(self.recurrent_shape or (0,)))

    def slot_nbytes(self) -> int:
        """Bytes a slot's state weighs over the group's layers, which is what
        a snapshot row weighs."""
        return self.num_layers * (
            self.rows * self.width * np.dtype(self.dtype).itemsize
            + self.recurrent_nbytes())

    def rows_in_use(self) -> int:
        """Snapshot rows that hold a snapshot: a slot's or the cache's."""
        return self.num_blocks - self.allocator.reserved \
            - self.allocator.free_blocks

    def snapshot_ends(self, start: int, end: int) -> List[int]:
        """The lengths in ``(start, end]`` a tail prefill of ``[start, end)``
        leaves a snapshot at, the most wanted first: the prompt's last whole
        block (where a replay of this very prompt hits), then every
        ``stride``-th absolute position, the farthest first.  Where a row is
        a recurrent state (megabytes) and the prompt ends on a stride, the
        replay's is left out: a prompt that goes on hits the strided one a
        block later, and a row is worth more than a replay's last stride."""
        last = (end - 1) // self.block_size * self.block_size
        ends = [last] if last > start and not (
            self.recurrent_shape and end % self.stride == 0) else []
        return ends + [e for e in range(end // self.stride * self.stride,
                                        start, -self.stride) if e != last]

    def _plan_row(self, first: int, at: Dict[int, int], start: int):
        row = np.full((1 + 2 * self.max_snaps,), self.num_blocks, np.int32)
        row[0] = first
        row[1 + self.max_snaps:] = 0
        for k, (end, snap) in enumerate(sorted(at.items())):
            row[1 + k], row[1 + self.max_snaps + k] = snap, end - start
        return row

    def begin_sequence(self, slot: int, first: int, start: int,
                       end: Optional[int], *,
                       write: bool = True) -> Tuple[int, int]:
        """The plan of the prefill program over ``[start, end)`` of ``slot``
        that starts from snapshot row ``first``: a row for each length of
        :meth:`snapshot_ends` the allocator can give (idle snapshots are
        evicted for them, oldest first; none is ever waited for).  ``end``
        None: a warm-up, no snapshot.  Returns ``(first, rows planned)``.
        ``write=False``: the device's plan is left to the caller
        (:meth:`plan_row`)."""
        if self._held[slot]:
            raise RuntimeError(f"slot {slot} already holds snapshot rows "
                               f"{self._held[slot]}")
        first = int(first)
        if end is not None:
            self.restored += first > ZERO_ROW
            self.cold += first == ZERO_ROW
        at: Dict[int, int] = {}
        if first > ZERO_ROW:
            # before the allocation: its eviction must not take the hit
            self.allocator.ref(first)
            self._held[slot].append(first)
        for e in self.snapshot_ends(start, end) if end is not None else []:
            got = self.allocator.alloc(1)
            if got is None:
                self.snapshots_skipped += 1
                continue
            at[e] = got[0]
        self._held[slot].extend(at.values())
        self._wrote[slot].update(at)
        self.snapshots_written += len(at)
        self._plans[slot] = self._plan_row(first, at, start)
        if write:
            self.plan._set_data(
                self.plan._value().at[slot].set(self._plans[slot]))
        return first, len(at)

    def plan_row(self, slot: int) -> np.ndarray:
        """``slot``'s plan as :meth:`begin_sequence` made it last."""
        return self._plans[slot]

    def release_slot(self, slot: int) -> None:
        held, self._held[slot] = self._held[slot], []
        self._wrote[slot] = {}
        for r in held:
            self.allocator.unref(r)

    def reset(self) -> None:
        for slot in range(self.num_slots):
            self.release_slot(slot)

    def wrote(self, slot: int) -> Dict[int, int]:
        return dict(self._wrote[slot])

    def check_invariants(self) -> List[str]:
        return self.allocator.check()

    def stats(self) -> dict:
        return {"snapshot_rows": self.num_blocks - self.allocator.reserved,
                "snapshot_rows_in_use": self.rows_in_use(),
                "stride": self.stride,
                "slot_bytes": self.slot_nbytes(),
                "state_bytes": self.num_slots * self.slot_nbytes(),
                "snapshot_pool_bytes": self.num_blocks * self.slot_nbytes(),
                "snapshots_written": self.snapshots_written,
                "snapshots_skipped": self.snapshots_skipped,
                "restored": self.restored, "cold": self.cold}

    # -- traced state ops -----------------------------------------------------

    def _planned(self, slot, S: int):
        """``(slot, row started from, rows to write [K], ends [K])`` of the
        ``S``-row program of ``slot``, from the device's plan."""
        K = min(self.max_snaps, S // self.stride + 1)
        s = _as_i32(slot).reshape(())
        plan = jax.lax.dynamic_index_in_dim(self.plan._value(), s, 0, False)
        return s, plan[0], plan[1:1 + K], \
            plan[1 + self.max_snaps:1 + self.max_snaps + K]

    def recurrent_start(self, layer_idx: int, slot, S: int):
        """What the recurrence of an ``S``-row tail prefill starts from and
        must hand back: ``(state [heads, d_k, d_v]`` of the snapshot row the
        slot's plan names, ``ends [K])``, the tail-relative row counts to
        return the state after (0: none)."""
        _, first, _, ends = self._planned(slot, S)
        pool = self.recurrent_snapshots[layer_idx]._value()
        return jax.lax.dynamic_index_in_dim(pool, first, 0, False), ends

    def recurrent_finish(self, layer_idx: int, slot, S: int, last, kept):
        """Takes back ``last [heads, d_k, d_v]``, the state at the tail's
        real end — the slot's from now on — and ``kept [K, ...]``, the states
        at :meth:`recurrent_start`'s ends, into the planned rows."""
        s, _, snaps, _ = self._planned(slot, S)
        st, pool = self.recurrent[layer_idx], \
            self.recurrent_snapshots[layer_idx]
        with jax.named_scope(STATE_WRITE_SCOPE):
            st._set_data(jax.lax.dynamic_update_index_in_dim(
                st._value(), last.astype(jnp.float32), s, 0))
            pool._set_data(pool._value().at[snaps].set(
                kept.astype(jnp.float32), mode="drop"))

    def recurrent_step(self, layer_idx: int, step, active):
        """A decode step: ``step(state [slots, heads, d_k, d_v], active)
        -> (out, state)`` rewrites the running slots' state in the layer's
        buffer (aliased: the others' bytes stay) and returns ``out``."""
        buf = self.recurrent[layer_idx]
        out, new = step(buf._value(), _as_i32(active))
        buf._set_data(new)
        return out

    def prefill_update(self, layer_idx: int, slot, z, start, length):
        """A tail prefill's columns ``z [1, S, width]`` at absolute positions
        ``start ..`` behind the state the slot's plan names; writes the
        slot's state from the tail's real end ``length`` (a bucket's pad rows
        reach no state) and the planned snapshots; returns the taps: ``rows +
        1`` views of ``ext [1, rows + S, width]``, tap ``k`` of position ``t``
        column ``t - (rows - k)``."""
        S = z.shape[1]
        s, first, snaps, ends = self._planned(slot, S)
        n = _as_i32(length).reshape(()) - _as_i32(start).reshape(())
        st, pool = self.state._value(), self.snapshots._value()
        ext = jnp.concatenate([pool[layer_idx, :, first][None],
                               z.astype(self.dtype)], axis=1)
        with jax.named_scope(STATE_WRITE_SCOPE):
            # (the slot's column written by a select over the layer's slab:
            # whole tiles, in place — a one-row write on the tiled dim makes
            # XLA:TPU move the array to another layout and back)
            mine = (jnp.arange(self.num_slots, dtype=jnp.int32)
                    == s)[None, :, None]
            cols = jnp.arange(self.rows, dtype=jnp.int32)
            new = jnp.take(ext[0], n + cols, axis=0)            # [rows, w]
            kept = jnp.take(ext[0], ends[:, None] + cols[None, :], axis=0)
            st = st.at[layer_idx].set(
                jnp.where(mine, new[:, None, :], st[layer_idx]))
            for r in range(self.rows):
                pool = pool.at[layer_idx, r, snaps].set(kept[:, r],
                                                        mode="drop")
            self.state._set_data(st)
            self.snapshots._set_data(pool)
        return [ext[:, k:k + S] for k in range(self.rows + 1)]

    def decode_update(self, layer_idx: int, z, active):
        """A decode step's columns ``z [slots, 1, width]``: the running
        slots' state shifts by one, the others' is left as it is; returns
        the taps."""
        st = self.state._value()
        cur = st[layer_idx]                              # [rows, slots, w]
        ext = jnp.concatenate([cur, z[:, 0].astype(self.dtype)[None]], axis=0)
        with jax.named_scope(STATE_WRITE_SCOPE):
            live = (_as_i32(active) > 0)[None, :, None]
            self.state._set_data(st.at[layer_idx].set(
                jnp.where(live, ext[1:], cur)))
        return [ext[k][:, None, :] for k in range(self.rows + 1)]


class GroupedKVCache:
    """The groups' pools behind the one cache's surface: ``pools``, a
    :class:`~.paging.PagedKVCache` each group that keeps tokens, and
    ``states``, a :class:`StatePool` each group that keeps state.  A hit, a
    slot's holdings and ``num_blocks`` are given in that order: the pools',
    then the states'."""

    def __init__(self, groups: Sequence[CacheGroup], *, num_slots: int,
                 max_seq: int, dtype, block_size: int = 16,
                 num_blocks: Sequence[Optional[int]] = (),
                 kernel: str = "reference", max_tail: Optional[int] = None):
        sizes = list(num_blocks) + [None] * (len(groups) - len(num_blocks))
        self.groups = tuple(groups)
        self.pools: List[PagedKVCache] = []
        self.states: List[StatePool] = []
        by_group = []
        for g, n in zip(self.groups, sizes):
            if g.state:
                self.states.append(StatePool(
                    num_slots, len(g.layers), g.sides, dtype,
                    block_size=block_size, num_snapshots=n,
                    max_tail=max_tail or max_seq, stride=g.stride,
                    chunk=g.chunk))
                by_group.append(self.states[-1])
                continue
            if g.window and n is None:
                # every slot a window and the block being written, and two
                # of the longest tail beside a hit's window
                per = -(-g.window // block_size) + 1
                n = num_slots * per + per + 4 * g.window // block_size + 1
            self.pools.append(PagedKVCache(
                num_slots, len(g.layers), max_seq, sides=g.sides, dtype=dtype,
                block_size=block_size, num_blocks=n, kernel=kernel,
                window=g.window))
            by_group.append(self.pools[-1])
        first = self.pools[0]
        for p in self.pools[1:]:
            p.lengths = first.lengths       # one sequence, one length
        #: layer -> (its group's pool, its index among the group's layers)
        self._where = {layer: (p, i) for g, p in zip(self.groups, by_group)
                       for i, layer in enumerate(g.layers)}
        self.num_layers = len(self._where)
        self.block_size = first.block_size
        self.max_blocks_per_slot = first.max_blocks_per_slot
        self.kernel = kernel
        self.lengths = first.lengths
        #: admissions each pool refused for want of blocks
        self.deferred_by = [0] * len(self.pools)
        #: what the engine's gauges and the first group's prefix chain read
        self.allocator = first.allocator
        self.num_blocks = first.num_blocks
        #: ``(row started from, snapshot rows planned)`` a state group, of
        #: the last program planned (the engine's prefill span reads it)
        self.planned: List[Tuple[int, int]] = []

    # -- the pools as one ---------------------------------------------------

    def buffers(self):
        return [b for p in (*self.pools, *self.states) for b in p.buffers()]

    def nbytes(self) -> int:
        return sum(p.nbytes() for p in (*self.pools, *self.states))

    @property
    def copy_on_extends(self) -> int:
        return sum(p.copy_on_extends for p in self.pools)

    def usable_blocks(self) -> int:
        return min(p.usable_blocks() for p in self.pools)

    def group_stats(self) -> List[dict]:
        """A group a row: its layers, its window, its blocks and how many a
        live slot holds."""
        kept = [g for g in self.groups if not g.state]
        return [{"layers": len(g.layers), "window": g.window,
                 "blocks": p.num_blocks - p.allocator.reserved,
                 "used": p.allocator.used_blocks,
                 "cached_idle": p.allocator.idle_cached_blocks,
                 "released": p.blocks_released,
                 "alloc_failures": p.allocator.alloc_failures}
                for g, p in zip(kept, self.pools)] + [
            {"layers": p.num_layers, "state": [p.rows, p.width],
             "recurrent": list(p.recurrent_shape or ()),
             "state_bytes": p.num_slots * p.slot_nbytes(),
             "snapshot_pool_bytes": p.num_blocks * p.slot_nbytes(),
             "blocks": p.num_blocks - p.allocator.reserved,
             "used": p.allocator.used_blocks,
             "cached_idle": p.allocator.idle_cached_blocks,
             "alloc_failures": p.allocator.alloc_failures}
            for p in self.states]

    def warm_host_programs(self) -> None:
        for p in self.pools:
            p.warm_host_programs()

    def check_invariants(self) -> List[str]:
        return [f"group {i} (window {p.kv_window}): {v}"
                for i, p in enumerate(self.pools)
                for v in p.check_invariants()] + [
            f"state group {i}: {v}" for i, p in enumerate(self.states)
            for v in p.check_invariants()]

    def decode_chunk_tokens(self) -> Optional[int]:
        return self.pools[0].decode_chunk_tokens()

    def decode_items_fn(self):
        """Work items a layer, the layers' mean: a group with a window lists
        only the chunks that meet it, a state group none."""
        fns = [p.decode_items_fn() for p in self.pools]
        if fns[0] is None:
            return None
        shares = [p.num_layers / self.num_layers for p in self.pools]
        return lambda seq_len: round(sum(
            s * f(seq_len) for s, f in zip(shares, fns)), 2)

    # -- host-side slot lifecycle ---------------------------------------------

    def begin_sequence(self, slot: int, shared, prefix_len: int,
                       tail_bucket: int, *, total: int = 0,
                       reserve: Sequence[int] = (),
                       end: Optional[int] = None,
                       write: bool = True) -> bool:
        """One admission's storage from every pool, all or nothing; then the
        state groups' plans for the program over ``[prefix_len, end)``
        (snapshot rows as the allocator has them: never waited for).
        ``end`` None: a warm-up, which plans no snapshot."""
        shared = shared or ([],) * len(self.pools) \
            + (ZERO_ROW,) * len(self.states)
        reserve = list(reserve) or [0] * len(self.pools)
        for i, (p, blocks, r) in enumerate(zip(self.pools, shared, reserve)):
            if not p.begin_sequence(slot, blocks, prefix_len, tail_bucket,
                                    total=total, reserve=r, write=write):
                self.deferred_by[i] += 1
                for q in self.pools[:i]:
                    q.release_slot(slot)
                return False
        self.planned = [st.begin_sequence(slot, row, prefix_len, end,
                                          write=write)
                        for st, row in zip(self.states,
                                           shared[len(self.pools):])]
        return True

    def tables(self) -> List[Tensor]:
        """Every pool's block table, then every state group's plan."""
        return [p.block_tables for p in self.pools] \
            + [st.plan for st in self.states]

    def table_rows(self, slot: int) -> List[np.ndarray]:
        return [r for p in self.pools for r in p.table_rows(slot)] \
            + [st.plan_row(slot) for st in self.states]

    def growth_needs(self, slot: int, total: int) -> List[int]:
        return [p.growth_need(slot, total) for p in self.pools]

    def extend_tail(self, slot: int, start: int, tail_bucket: int, *,
                    write: bool = True) -> bool:
        """The blocks of the next piece of a prompt prefilled in pieces."""
        if self.states:
            raise NotImplementedError(
                "a prompt prefilled in pieces beside a group that keeps "
                "state: a tail prefill starts from a snapshot or from zeros, "
                "not from the state the piece before left")
        return all(p.extend_tail(slot, start, tail_bucket, write=write)
                   for p in self.pools)

    def ensure_capacity(self, slot: int, next_pos: int) -> bool:
        return all(p.ensure_capacity(slot, next_pos) for p in self.pools)

    def release_behind(self, slot: int, next_pos: int, *,
                       write: bool = True) -> int:
        return sum(p.release_behind(slot, next_pos, write=write)
                   for p in self.pools)

    def release_slot(self, slot: int) -> None:
        for p in (*self.pools, *self.states):
            p.release_slot(slot)

    def reset(self) -> None:
        for p in (*self.pools, *self.states):
            p.reset()

    def owned_blocks(self, slot: int) -> GroupHit:
        """What the slot holds that a later prompt can hit: its blocks, a
        list a pool, then ``{length: snapshot row}`` a state group."""
        return tuple(p.owned_blocks(slot) for p in self.pools) \
            + tuple(st.wrote(slot) for st in self.states)

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

    def latent_prefill_write(self, layer_idx: int, slot, lat, start) -> None:
        pool, i = self._where[layer_idx]
        pool.latent_prefill_write(i, slot, lat, start)

    def latent_prefill_attention(self, layer_idx: int, slot, q, lat, w_uk,
                                 w_uv, start, length, *, scale: float):
        pool, i = self._where[layer_idx]
        return pool.latent_prefill_attention(i, slot, q, lat, w_uk, w_uv,
                                             start, length, scale=scale)

    def latent_decode_attention(self, layer_idx: int, q_lat, lat, active, *,
                                scale: float, dv: int):
        pool, i = self._where[layer_idx]
        return pool.latent_decode_attention(i, q_lat, lat, active,
                                            scale=scale, dv=dv)

    def recurrent_start(self, layer_idx: int, slot, S: int):
        pool, i = self._where[layer_idx]
        return pool.recurrent_start(i, slot, S)

    def recurrent_finish(self, layer_idx: int, slot, S: int, last, kept):
        pool, i = self._where[layer_idx]
        pool.recurrent_finish(i, slot, S, last, kept)

    def recurrent_step(self, layer_idx: int, step, active):
        pool, i = self._where[layer_idx]
        return pool.recurrent_step(i, step, active)

    def state_prefill(self, layer_idx: int, slot, z, start, length):
        pool, i = self._where[layer_idx]
        return pool.prefill_update(i, slot, z, start, length)

    def state_decode(self, layer_idx: int, z, active):
        pool, i = self._where[layer_idx]
        return pool.decode_update(i, z, active)


class GroupedPrefixCache:
    """The prefix cache of a :class:`GroupedKVCache`: one
    :class:`~.prefix_cache.PrefixCache` a group over its allocator, all keyed
    by the same chain hash from the prompt's start.  A group that keeps all
    is a chain (a hit is a contiguous prefix, leaves are evicted first); a
    group with a window keeps runs of entries that start in mid-prompt and is
    evicted oldest first; a state group keeps single entries — a snapshot row
    under the key of the block that ends at its length — evicted oldest first
    too.  The engine's interface is the one cache's."""

    def __init__(self, cache: GroupedKVCache):
        self.cache = cache
        self.block_size = cache.block_size
        self.chains = [PrefixCache(p.allocator, p.block_size,
                                   chained=not p.kv_window)
                       for p in cache.pools]
        self.state_chains = [PrefixCache(st.allocator, cache.block_size,
                                         chained=False)
                             for st in cache.states]
        #: hits that ended short of what the groups that keep all had, for
        #: want of a window's blocks or of a snapshot, and the tokens they
        #: gave up (in all, and in the last lookup)
        self.hits_shortened = 0
        self.tokens_given_up = 0
        self.last_given_up = 0

    @property
    def epoch(self) -> int:
        return self.chains[0].epoch

    def _first_needed(self, pool: PagedKVCache, end: int) -> int:
        """The first block a tail that starts at block ``end`` reads of
        ``pool``: block 0, or the block of its first query's oldest key."""
        return max(0, end * self.block_size - pool.kv_window + 1) \
            // self.block_size if pool.kv_window else 0

    def _walk(self, prompt, salt: bytes, max_tokens: Optional[int],
              memo: Optional[ChainKeys] = None):
        """``(end, kept, keys)``: the hit's end in blocks, the end the groups
        that keep all would allow, and the chain keys up to there."""
        prompt = as_tokens(prompt)
        bs = self.block_size
        stop = max(0, (int(prompt.size) - 1) // bs)
        if max_tokens is not None:
            stop = min(stop, int(max_tokens) // bs)
        keys = self.chains[0]._keys_for(prompt, stop, salt, memo)
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
            entries, behind, run = chain._entries, pool.kv_window - 1, 0
            for end in range(1, kept + 1):
                run = run + 1 if keys[end - 1] in entries else 0
                # (``_first_needed(pool, end)``, in line: once a block)
                if run < end - max(0, end * bs - behind) // bs:
                    ok[end] = False
        # and every state group a snapshot at exactly that length (none is
        # needed at 0: the zeros)
        for chain in self.state_chains:
            for end in range(1, kept + 1):
                ok[end] = ok[end] and keys[end - 1] in chain._entries
        end = max(e for e in range(kept + 1) if ok[e])
        return end, kept, keys

    def lookup(self, prompt, count: bool = True, salt: bytes = b"",
               max_tokens: Optional[int] = None,
               keys: Optional[ChainKeys] = None):
        """``(n_tokens, block ids by position a pool, then the snapshot row
        a state group)``; ``max_tokens`` caps the hit's end; ``keys``: the
        prompt's :class:`~.prefix_cache.ChainKeys` where the caller keeps
        them."""
        end, kept, keys = self._walk(prompt, salt, max_tokens, keys)
        self.hits_shortened += end < kept
        self.last_given_up = (kept - end) * self.block_size
        self.tokens_given_up += self.last_given_up
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
        for chain in self.state_chains:
            row = ZERO_ROW
            if end:
                e = chain._entries[keys[end - 1]]
                e.hits += 1
                chain._entries.move_to_end(keys[end - 1])
                row = e.block_id
            hit.append(row)
        if count:
            self.record_lookup(len(prompt), end * self.block_size)
        return end * self.block_size, tuple(hit)

    def probe(self, prompt, salt: bytes = b"") -> int:
        return self._walk(prompt, salt, None)[0] * self.block_size

    def record_lookup(self, prompt_tokens: int, hit_tokens: int) -> None:
        self.chains[0].record_lookup(prompt_tokens, hit_tokens)

    def register(self, prompt, owned: GroupHit, salt: bytes = b"",
                 hit_tokens: int = 0,
                 keys: Optional[ChainKeys] = None) -> int:
        """The prompt's whole blocks of a group that keeps all; of a state
        group the snapshots the slot's prefill wrote, each at its length; of
        a group with a window the blocks the slot still holds: its last
        window's.
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
            n += chain.register(prompt, blocks, salt=salt, first_block=first,
                                keys=keys)
            if pool.kv_window and 0 < hit_end <= first:
                moved_past = chain._keys_for(as_tokens(prompt), hit_end, salt,
                                             keys)
                # from where the run of a prompt that ended there starts:
                # what the longest hit of that very prompt reads
                for key in moved_past[self._first_needed(pool, hit_end - 1):]:
                    e = chain._entries.get(key)
                    if e is not None and \
                            pool.allocator.refcount(e.block_id) == 1:
                        chain._evict_one(key)
        for chain, wrote in zip(self.state_chains, owned[len(self.chains):]):
            n += chain.register_at(prompt, wrote, salt=salt, keys=keys)
        return n

    def bump_epoch(self) -> int:
        return [c.bump_epoch()
                for c in (*self.chains, *self.state_chains)][0]

    def clear(self) -> int:
        return sum(c.clear() for c in (*self.chains, *self.state_chains))

    def __len__(self) -> int:
        return sum(len(c) for c in (*self.chains, *self.state_chains))

    def hit_rate(self) -> float:
        return self.chains[0].hit_rate()

    def stats(self) -> dict:
        s = self.chains[0].stats()
        s["group_entries"] = [len(c) for c in self.chains]
        s["group_evictions"] = [c.evictions for c in self.chains]
        s["hits_shortened"] = self.hits_shortened
        s["tokens_given_up"] = self.tokens_given_up
        if self.state_chains:
            s["snapshot_entries"] = [len(c) for c in self.state_chains]
            s["snapshot_evictions"] = [c.evictions
                                       for c in self.state_chains]
        return s

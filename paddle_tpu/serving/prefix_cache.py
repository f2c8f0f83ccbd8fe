"""Cross-request prefix reuse: a host-side hash-chained block cache.

``PrefixCache`` maps whole prompt token *blocks* to KV-pool block ids so a
request whose prompt starts with a previously-served prefix skips
re-prefilling the shared span: the engine looks the prompt up, maps the
hit to refcounted shared blocks in the :class:`~.paging.BlockAllocator`
pool, and prefills only the uncached tail bucket.

Design points (all host-side — nothing here ever enters a trace, so the
zero-recompile property of the serving engine is untouchable from this
module):

- **Whole blocks only.**  A block of ``block_size`` tokens is the unit of
  both storage and matching: partial-block hits would share K/V lines that
  a later request must append into, which is exactly the aliasing the
  block-granular design avoids.
- **Hash-chained keys.**  Block ``i``'s key is
  ``H(key[i-1] || tokens[i*bs:(i+1)*bs])``, so a lookup hit is always a
  *contiguous prefix*: the walk stops at the first absent link and can
  never skip-match an interior block.
- **Capped below the full prompt.**  At most ``(len(prompt) - 1) // bs``
  blocks can hit, so the uncached tail always holds >= 1 token — the
  engine still runs a real prefill and gets first-token logits, and a
  tail write never lands inside a shared block (copy-on-extend stays a
  defensive path, not a steady-state one).
- **One reference per cached block.**  Registering a block takes a single
  allocator ref on behalf of the cache; live slots stack their own refs
  on top.  Evicting an entry drops only the cache's ref — blocks still
  referenced by running requests stay alive (they just stop being
  hittable).
- **LRU, leaf-first eviction.**  Entries are kept in recency order and
  only chain *leaves* (entries with no cached children) are evictable, so
  the cache always stores contiguous chains; candidates must also be
  idle (refcount 1 — the cache's own ref) or evicting them would free
  nothing.
- **Version epoch.**  Cached K/V bytes are a function of the *weights*
  that prefilled them, so a rolling weight hot-swap must make every
  pre-swap block unhittable: :meth:`bump_epoch` folds a monotonically
  increasing epoch into the chain-hash ROOT.  A lookup under epoch
  ``N+1`` can never match an entry registered under epoch ``N`` — the
  keys live in disjoint hash domains by construction, which is a
  stronger guarantee than clearing (there is no window where a stale
  entry is still reachable).  The bump also drops every idle entry so
  the old-weight blocks return to the pool.
- **Tenant salt.**  Multi-LoRA serving makes cached K/V a function of
  the *adapter* that prefilled it too, so every lookup/register/probe
  takes a ``salt`` (``b""`` for the base model, ``b"name@vN"`` from
  :meth:`~.adapters.AdapterPool.salt` for a tenant) folded into the
  chain-hash root alongside the epoch.  Tenant KV can never cross-hit
  another tenant — or a stale version of itself — by the same
  disjoint-domain argument as the epoch.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["PrefixCache", "ChainKeys", "as_tokens"]

_ROOT = b"paddle-tpu-prefix-root"


def as_tokens(prompt) -> np.ndarray:
    """``prompt`` as the flat ``int64`` array the keys are hashed from: a
    request's ``prompt_ids`` as it is, anything else converted once."""
    return np.asarray(prompt, dtype=np.int64).reshape(-1)


def _chain(prompt: np.ndarray, root: bytes, block_size: int,
           n_blocks: int) -> List[bytes]:
    """The chain keys of ``prompt``'s first ``n_blocks`` blocks: block ``i``'s
    is ``H(key[i-1] || its tokens as int64)``, the first one's parent
    ``root``.  The prompt's bytes are taken once and hashed by slices."""
    buf = memoryview(np.ascontiguousarray(prompt, dtype=np.int64)).cast("B")
    step = 8 * block_size
    keys, parent = [], root
    for lo in range(0, n_blocks * step, step):
        h = hashlib.blake2b(parent, digest_size=16)
        h.update(buf[lo:lo + step])
        parent = h.digest()
        keys.append(parent)
    return keys


class ChainKeys:
    """One prompt's chain keys, kept between the calls that read them: a
    request holds one, and its lookup, its capped re-lookup, its registration
    and every retry of a deferred or preempted admission pass it as
    ``keys=``.  The keys are kept by block size under the root (epoch and
    salt) they were hashed from; a call under another root — the weights
    were swapped, the tenant's adapter moved — drops them and walks the
    prompt again.  ``passes`` counts those walks."""

    __slots__ = ("root", "by_block", "passes")

    def __init__(self):
        self.root: Optional[bytes] = None
        self.by_block: Dict[int, List[bytes]] = {}
        self.passes = 0


@dataclass
class _Entry:
    block_id: int
    parent: Optional[bytes]
    children: int = 0
    depth: int = 0                      # chain position (0 = first block)
    hits: int = field(default=0)


class PrefixCache:
    """Host-side chained-hash map from prompt blocks to pool block ids."""

    def __init__(self, allocator, block_size: int, chained: bool = True):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.allocator = allocator
        self.block_size = int(block_size)
        #: False: entries keep no parent link, so every idle one is
        #: evictable, oldest first — the cache of a group that lets blocks
        #: go behind a window, whose runs of entries start in mid-prompt and
        #: lose their *oldest* block first (the keys are still the chain
        #: hashes from the prompt's start)
        self.chained = bool(chained)
        self._entries: "OrderedDict[bytes, _Entry]" = OrderedDict()
        #: weight-version epoch: folded into every chain-hash root, so
        #: entries registered under an older epoch are unreachable by
        #: construction (rolling hot-swap correctness — see module doc)
        self.epoch = 0
        # counters (exported via Engine metrics)
        self.lookups = 0
        self.hit_blocks_total = 0
        self.hit_tokens_total = 0
        self.lookup_tokens_total = 0
        self.evictions = 0
        # the allocator reclaims idle cached blocks through this hook
        allocator.evict_cb = self._evict_for_alloc

    # -- lookup / register -------------------------------------------------

    def _keys_for(self, prompt: np.ndarray, n_blocks: int,
                  salt: bytes = b"",
                  keys: Optional[ChainKeys] = None) -> List[bytes]:
        """Chain keys of ``prompt``'s first ``n_blocks`` blocks under this
        cache's epoch and ``salt``; read from ``keys`` where the caller keeps
        them (all of the prompt's whole blocks are hashed on the first call
        under a root, so no later call hashes again)."""
        root = _ROOT + self.epoch.to_bytes(8, "little") + salt
        if keys is None:
            return _chain(prompt, root, self.block_size, n_blocks)
        if keys.root != root:
            keys.root, keys.by_block = root, {}
            keys.passes += 1
        have = keys.by_block.get(self.block_size)
        if have is None:
            have = keys.by_block[self.block_size] = _chain(
                prompt, root, self.block_size,
                int(prompt.size) // self.block_size)
        return have[:n_blocks]

    def record_lookup(self, prompt_tokens: int, hit_tokens: int) -> None:
        """Count one logical lookup toward the hit-rate gauges.  The
        engine calls this only for results it actually USED (and once
        per request, not per deferral retry), so ``hit_rate`` never
        credits tokens that were re-prefilled anyway — discarded
        (over-budget) and raising lookups are recorded as misses."""
        self.lookups += 1
        self.lookup_tokens_total += int(prompt_tokens)
        self.hit_blocks_total += int(hit_tokens) // self.block_size
        self.hit_tokens_total += int(hit_tokens)

    def lookup(self, prompt: Sequence[int], count: bool = True,
               salt: bytes = b"", first_block: int = 0,
               max_blocks: Optional[int] = None,
               keys: Optional[ChainKeys] = None) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``prompt``: ``(n_tokens, block_ids)``.

        Walks the hash chain over whole prompt blocks, stopping at the
        first absent link; capped so at least one prompt token is always
        left for the tail prefill.  Touches every hit entry (LRU refresh)
        but takes NO references — the caller refs the blocks it actually
        admits a sequence onto.  ``count=False`` skips the hit-rate
        counters — the engine counts via :meth:`record_lookup` instead,
        after it has decided whether the result is actually used.
        ``first_block``: the walk starts at that block of the prompt (the
        blocks before it are another group's to cover) and takes at most
        ``max_blocks``; the tokens returned are those of the walk alone.
        ``keys``: the prompt's :class:`ChainKeys`, where the caller keeps
        them."""
        prompt = as_tokens(prompt)
        if count:
            self.lookups += 1
            self.lookup_tokens_total += int(prompt.size)
        block_ids: List[int] = []
        for key in self._span(prompt, salt, first_block, max_blocks, keys):
            e = self._entries.get(key)
            if e is None:
                break
            e.hits += 1
            self._entries.move_to_end(key)
            block_ids.append(e.block_id)
        if count:
            self.hit_blocks_total += len(block_ids)
            self.hit_tokens_total += len(block_ids) * self.block_size
        return len(block_ids) * self.block_size, block_ids

    def _span(self, prompt: np.ndarray, salt: bytes, first_block: int,
              max_blocks: Optional[int],
              keys: Optional[ChainKeys] = None) -> List[bytes]:
        """Chain keys of the blocks a walk may take: from ``first_block``,
        at most ``max_blocks``, and never the prompt's last token's."""
        stop = max(0, (int(prompt.size) - 1) // self.block_size)
        if max_blocks is not None:
            stop = min(stop, first_block + max_blocks)
        return self._keys_for(prompt, stop, salt, keys)[first_block:]

    def probe(self, prompt: Sequence[int], salt: bytes = b"",
              first_block: int = 0, max_blocks: Optional[int] = None) -> int:
        """Side-effect-free longest-cached-prefix length in TOKENS: no
        LRU refresh, no hit/lookup counters, no references taken.  The
        fleet router's affinity probe — it may interrogate every
        replica's cache per dispatch, and only the chosen replica's
        recency order and hit-rate gauges should move (they do, at
        admission, through the real :meth:`lookup`)."""
        prompt = as_tokens(prompt)
        n = 0
        for key in self._span(prompt, salt, first_block, max_blocks):
            if key not in self._entries:
                break
            n += 1
        return n * self.block_size

    def register(self, prompt: Sequence[int], block_ids: Sequence[int],
                 salt: bytes = b"", first_block: int = 0,
                 keys: Optional[ChainKeys] = None) -> int:
        """Make ``prompt``'s whole blocks hittable by later requests.

        ``block_ids`` must cover the prompt's full blocks in order (the
        slot's table prefix).  Blocks already registered under the same
        chain key are left as-is (first writer wins — the bytes are
        bitwise-identical by construction); each newly-registered block
        takes one allocator ref on behalf of the cache.  Returns how many
        new entries were created.  ``first_block``: registration starts at
        that block (a chain of its own: the blocks before it are another
        group's) and ends at the first block the slot has released (id 0)."""
        prompt = as_tokens(prompt)
        n_full = min(int(prompt.size) // self.block_size, len(block_ids))
        created, parent = 0, None
        chain = self._keys_for(prompt, n_full, salt, keys)
        for depth in range(first_block, n_full):
            key = chain[depth]
            if not block_ids[depth]:
                break
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                if self.chained:
                    parent = key
                continue
            self._entries[key] = _Entry(
                block_id=int(block_ids[depth]), parent=parent, depth=depth)
            self.allocator.ref(int(block_ids[depth]))
            self.allocator.mark_cached(int(block_ids[depth]))
            if parent is not None:
                self._entries[parent].children += 1
            if self.chained:
                parent = key
            created += 1
        return created

    def register_at(self, prompt: Sequence[int], at, salt: bytes = b"",
                    keys: Optional[ChainKeys] = None) -> int:
        """Single entries of a cache that keeps no chain (``chained=False``):
        ``at = {tokens: id}`` puts ``id`` under the key of the block that
        *ends* at ``tokens`` (a whole number of blocks of ``prompt``) — what
        is kept there is a property of the whole prefix up to that length,
        not of the block.  First writer wins; returns the entries created."""
        if not at:
            return 0
        chain = self._keys_for(as_tokens(prompt),
                               max(at) // self.block_size, salt, keys)
        created = 0
        for tokens, ident in sorted(at.items()):
            key = chain[tokens // self.block_size - 1]
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            self._entries[key] = _Entry(block_id=int(ident), parent=None,
                                        depth=tokens // self.block_size - 1)
            self.allocator.ref(int(ident))
            self.allocator.mark_cached(int(ident))
            created += 1
        return created

    # -- eviction ----------------------------------------------------------

    def _evictable(self) -> Optional[bytes]:
        """Oldest leaf entry whose block is idle (cache holds the only
        ref) — evicting anything else would either break a chain or free
        nothing."""
        for key, e in self._entries.items():
            if e.children == 0 and self.allocator.refcount(e.block_id) == 1:
                return key
        return None

    def _evict_one(self, key: bytes) -> None:
        e = self._entries.pop(key)
        if e.parent is not None and e.parent in self._entries:
            self._entries[e.parent].children -= 1
        self.allocator.unmark_cached(e.block_id)
        self.allocator.unref(e.block_id)
        self.evictions += 1

    def _evict_for_alloc(self, n_blocks: int) -> int:
        """Allocator pressure hook: free up to ``n_blocks`` idle cached
        blocks, LRU leaf-first.  Returns how many were freed."""
        freed = 0
        while freed < n_blocks:
            key = self._evictable()
            if key is None:
                break
            self._evict_one(key)
            freed += 1
        return freed

    def bump_epoch(self) -> int:
        """Invalidate every cached block for a weight hot-swap: advance
        the epoch (new lookups/registrations hash in a disjoint domain —
        an old-epoch entry can never prefix-hit again) and drop every
        idle entry so the stale-KV blocks return to the pool.  Entries
        still pinned by live slots keep their refs until those slots
        release — they are unreachable either way.  Returns the new
        epoch."""
        self.epoch += 1
        self.clear()
        return self.epoch

    def clear(self) -> int:
        """Drop every entry (releasing the cache's refs).  Returns the
        number of entries dropped."""
        n = 0
        while self._entries:
            key = self._evictable()
            if key is None:
                # remaining entries are pinned by live slots: drop the
                # cache's view of them anyway (refs released, chains gone)
                key = next(iter(self._entries))
            self._evict_one(key)
            n += 1
        return n

    # -- introspection -----------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def hit_rate(self) -> float:
        """Fraction of looked-up prompt tokens served from cache."""
        return self.hit_tokens_total / self.lookup_tokens_total \
            if self.lookup_tokens_total else 0.0

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "epoch": self.epoch,
            "lookups": self.lookups,
            "hit_blocks": self.hit_blocks_total,
            "hit_tokens": self.hit_tokens_total,
            "lookup_tokens": self.lookup_tokens_total,
            "hit_rate": round(self.hit_rate(), 4),
            "evictions": self.evictions,
        }

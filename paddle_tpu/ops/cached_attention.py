"""Decode-step attention against the serving caches.

Serving counterpart of ``ops.pallas.flash_attention``: during continuous-
batching decode every sequence contributes exactly ONE query token, and the
keys/values live in a preallocated fixed-shape cache, so the dense read
(:func:`cached_attention` — what the speculative draft's
``serving.KVCache`` uses, and the oracle of the paged reads after
:func:`gather_block_kv`) is a masked single-row attention over
``[B, T, Hkv, D]`` where T is the cache capacity; the ``paged_*`` reads
consume the engine pool's block table inside a Pallas kernel.  Static shapes are the point: the same
compiled executable serves every step of every request (XLA recompiles on
any new shape — FlashFuser-style fused decode attention assumes exactly
this fixed-layout cache).

GQA is handled inside the kernel: ``Hkv`` may divide ``H`` and kv heads are
repeated consecutively (kv head ``h // (H // Hkv)`` serves query head
``h``), matching the models' no-cache expand path bit-for-bit.

The XLA formulations below are the oracle path; on TPU each is already a
single fused masked-softmax-matmul under jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op

__all__ = ["cached_attention", "gather_block_kv",
           "block_prefill_attention", "paged_decode_attention",
           "paged_prefill_attention", "verify_attention"]


def cached_attention(query, k_cache, v_cache, lengths, window=0, name=None):
    """One decode step of attention for a batch of cache slots.

    Args:
        query:   ``[B, 1, H, D]`` — the current token's projected queries.
        k_cache: ``[B, T, Hkv, D]`` — per-slot key cache (one layer),
                 positions ``0..lengths[b]`` valid (current token included:
                 the caller writes the new K/V *before* attending).
        v_cache: ``[B, T, Hkv, D]`` — per-slot value cache.
        lengths: ``[B]`` int32 — index of the current token per slot; the
                 attention window is ``0..lengths[b]`` inclusive.
        window:  0, or the keys a query reads: its own position and the
                 ``window - 1`` before it.

    Returns:
        ``[B, 1, H, D]`` context tensor.
    """

    def _primal(q, k, v, ln):
        B, Sq, H, D = q.shape
        T, Hkv = k.shape[1], k.shape[2]
        if Hkv != H:
            rep = H // Hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / (D ** 0.5)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = logits.astype(jnp.float32)
        kpos = jnp.arange(T, dtype=ln.dtype)[None, :]
        valid = kpos <= ln[:, None]                                   # [B,T]
        if window:
            valid &= kpos > ln[:, None] - window
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    return apply_op("cached_attention", _primal,
                    [query, k_cache, v_cache, lengths])


def verify_attention(query, k_cache, v_cache, lengths, name=None):
    """Speculative-decoding verify attention: W tokens per slot in one
    fixed-shape step (:func:`cached_attention` generalized from W = 1).

    The verify step of a draft-propose / target-verify round scores the
    last emitted token plus the k draft proposals — W = k + 1 query
    tokens per slot sitting at absolute positions
    ``lengths[b] .. lengths[b] + W - 1`` — against the slot's cache in
    ONE forward, so speculation adds a single compiled program instead
    of k sequential target steps.

    Args:
        query:   ``[B, W, H, D]`` — the verify window's queries.
        k_cache: ``[B, T, Hkv, D]`` — per-slot key cache (one layer),
                 positions ``0..lengths[b]+W-1`` valid (the window's
                 K/V already written by the caller).
        v_cache: ``[B, T, Hkv, D]`` — per-slot value cache.
        lengths: ``[B]`` int32 — absolute position of the window's
                 FIRST query; query ``i`` attends ``0..lengths[b]+i``
                 inclusive (the causal mask, per-slot offset).

    Returns:
        ``[B, W, H, D]`` context tensor.  GQA kv heads repeat
        consecutively inside, matching :func:`cached_attention`
        bit-for-bit at W = 1.
    """

    def _primal(q, k, v, ln):
        B, W, H, D = q.shape
        T, Hkv = k.shape[1], k.shape[2]
        if Hkv != H:
            rep = H // Hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / (D ** 0.5)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = logits.astype(jnp.float32)
        qpos = ln[:, None] + jnp.arange(W, dtype=ln.dtype)[None, :]  # [B,W]
        kpos = jnp.arange(T, dtype=ln.dtype)                         # [T]
        valid = kpos[None, None, :] <= qpos[:, :, None]              # [B,W,T]
        logits = jnp.where(valid[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    return apply_op("verify_attention", _primal,
                    [query, k_cache, v_cache, lengths])


def gather_block_kv(pool_layer, block_tables):
    """Gather one layer of a paged KV pool back into contiguous per-slot
    sequences (the decode read of the paged cache).

    Args:
        pool_layer:   ``[num_blocks, block_size, Hkv, D]`` — one layer's
                      slice of the block pool.
        block_tables: ``[B, max_blocks]`` int32 — per-slot block ids.

    Returns:
        ``[B, max_blocks * block_size, Hkv, D]`` — each slot's sequence
        laid out contiguous, garbage past ``lengths[b]`` (the caller's
        attention mask never reads it).  Shapes depend only on
        (slots, block_size, max_blocks): the gather indices are *values*,
        so one executable serves every block-table content.
    """
    B, MB = block_tables.shape
    bs = pool_layer.shape[1]
    g = jnp.take(pool_layer, block_tables.reshape(-1), axis=0)
    return g.reshape(B, MB * bs, *pool_layer.shape[2:])


def block_prefill_attention(query, k_cache, v_cache, start, window=0,
                            name=None):
    """Tail-bucket prefill attention against a block-gathered cache.

    The paged serving path prefills only the *uncached tail* of a prompt:
    queries are the tail's S tokens at absolute positions
    ``start .. start+S-1``, while keys/values are the slot's ENTIRE
    gathered sequence (shared prefix blocks + the tail just written), so
    one masked attention covers both cross-attention onto the cached
    prefix and causal attention within the tail.

    Args:
        query:   ``[1, S, H, D]`` — tail queries (S = tail bucket).
        k_cache: ``[1, T, Hkv, D]`` — gathered keys
                 (``T = max_blocks_per_slot * block_size``); positions
                 ``0..start-1`` hold the cached prefix, ``start..``
                 the freshly-written tail.
        v_cache: ``[1, T, Hkv, D]`` — gathered values.
        start:   scalar int32 — absolute position of the first query.
        window:  0, or the keys a query reads (``j <= i`` AND ``j > i -
                 window``).

    Returns:
        ``[1, S, H, D]`` context tensor.  GQA kv heads are repeated
        consecutively inside, matching ``cached_attention`` bit-for-bit.
    """

    def _primal(q, k, v, st):
        B, S, H, D = q.shape
        T, Hkv = k.shape[1], k.shape[2]
        if Hkv != H:
            rep = H // Hkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        scale = 1.0 / (D ** 0.5)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        logits = logits.astype(jnp.float32)
        st = jnp.asarray(st).astype(jnp.int32).reshape(())
        qpos = st + jnp.arange(S, dtype=jnp.int32)            # [S]
        kpos = jnp.arange(T, dtype=jnp.int32)                 # [T]
        valid = kpos[None, :] <= qpos[:, None]                # [S, T]
        if window:
            valid &= kpos[None, :] > qpos[:, None] - window
        logits = jnp.where(valid[None, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    return apply_op("block_prefill_attention", _primal,
                    [query, k_cache, v_cache, start])


def _paged_per_shard(kernel, args, mesh):
    """Run a paged kernel once per shard of a sharded engine's ``mesh``
    (``ops.pallas.per_shard``): queries ``[B, S, H, D]`` and pool layers
    ``[blocks, block_size, Hkv, Dp]`` both carry heads at dim 2 and split
    there over the model axis, like the weights and the KV pool; the block
    table and the per-slot or scalar arguments after it replicate."""
    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding_spec import MODEL_AXIS
    from .pallas import per_shard

    heads = P(None, None, MODEL_AXIS, None)
    return per_shard(kernel, args,
                     (heads,) * 3 + (P(),) * (len(args) - 3), mesh)


def paged_decode_attention(query, k_pool, v_pool, block_tables, lengths,
                           active, interpret=False, mesh=None, window=0,
                           name=None):
    """Flash-decoding paged attention: the Pallas kernel path of the
    decode read (``ops.pallas.paged_attention_kernel``), consuming the
    block table *inside* the kernel — the fused replacement for
    ``gather_block_kv`` + :func:`cached_attention` (which remain the
    ``kernel="reference"`` oracle).

    Args:
        query:        ``[B, 1, H, D]`` current-token queries.
        k_pool:       ``[num_blocks, block_size, Hkv, Dp]`` one layer of
                      the paged key pool (current token already written;
                      ``Dp >= D``, lanes past ``D`` zero).
        v_pool:       same for values.
        block_tables: ``[B, max_blocks]`` int32 per-slot block ids.
        lengths:      ``[B]`` int32 current token index per slot.
        active:       ``[B]`` int32, nonzero for the running slots: the
                      kernel visits no other slot.
        interpret:    run the kernel in Pallas interpret mode (the
                      CPU/tier-1 path; False compiles for real TPUs).
        mesh:         the mesh a sharded engine's pool lives on (None:
                      unsharded) — the kernel then runs per head shard.
        window:       0, or the keys a query reads (the kernel's own).

    Returns:
        ``[B, 1, H, D]`` context, GQA expanded inside the kernel; zero
        for the slots that are not active.
    """
    from .pallas.paged_attention_kernel import paged_decode_attention_kernel

    def _primal(q, kp, vp, tbl, ln, act):
        return _paged_per_shard(
            functools.partial(paged_decode_attention_kernel,
                              window=window, interpret=interpret),
            (q, kp, vp, tbl, ln, act), mesh)

    return apply_op("paged_decode_attention", _primal,
                    [query, k_pool, v_pool, block_tables, lengths, active])


def paged_prefill_attention(query, k_pool, v_pool, block_row, start,
                            interpret=False, mesh=None, window=0, name=None,
                            length=None):
    """Fused cached-prefix + causal-tail prefill attention: the Pallas
    kernel path of the paged tail prefill, reading the slot's block
    row straight off the pool — the fused replacement for
    ``gather_block_kv`` + :func:`block_prefill_attention`.

    Args:
        query:     ``[1, S, H, D]`` tail queries (S = tail bucket).
        k_pool:    ``[num_blocks, block_size, Hkv, Dp]`` layer key pool
                   (``Dp >= D``, lanes past ``D`` zero).
        v_pool:    same for values.
        block_row: ``[max_blocks]`` int32 — the slot's block-table row.
        start:     scalar int32 — absolute position of the first query.
        interpret: Pallas interpret mode (CPU/tier-1 path).
        mesh:      the mesh a sharded engine's pool lives on (None:
                   unsharded) — the kernel then runs per head shard.
        window:    0, or the keys a query reads (the kernel's own).
        length:    scalar int32 — the prompt's real length so far (rows
                   ``length - start ..`` of the bucket are padding: the
                   kernel visits no query tile without a real row and
                   returns zeros there); None: every row is real.

    Returns:
        ``[1, S, H, D]`` context.
    """
    from .pallas.paged_attention_kernel import paged_prefill_attention_kernel

    def _primal(q, kp, vp, row, st, *ln):
        return _paged_per_shard(
            functools.partial(paged_prefill_attention_kernel,
                              window=window, interpret=interpret),
            (q, kp, vp, row, jnp.asarray(st).reshape(1),
             *map(jnp.asarray, ln)), mesh)

    return apply_op("paged_prefill_attention", _primal,
                    [query, k_pool, v_pool, block_row, start]
                    + ([] if length is None else [length]))

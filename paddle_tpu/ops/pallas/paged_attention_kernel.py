"""Pallas TPU paged attention: block-table-consuming decode + fused
cached-prefix/causal-tail prefill kernels for the paged serving path.

Reference parity: the jnp formulation in ``ops.cached_attention``
(``gather_block_kv`` + ``cached_attention`` for decode,
``gather_block_kv`` + ``block_prefill_attention`` for tail prefill) —
re-designed flash-decoding style (FlashFuser, arXiv:2512.12949: one
kernel scope over the cached prefix and the causal tail) so the block
table is consumed *inside* the kernel instead of first materializing a
contiguous ``[slots, max_blocks * block_size, Hkv, D]`` copy of every
slot's K/V in HBM:

- **decode** (``paged_decode_attention``): grid ``(slots, max_blocks)``;
  the block table and lengths ride in scalar-prefetch SMEM, and each
  grid step DMAs exactly ONE ``[block_size, Hkv, D]`` K/V block —
  selected by the table *value*, the automatic-kernel-generation move of
  arXiv:2006.12645 (the index map is data-driven, the kernel is not
  specialized per table) — accumulating an online softmax per query
  head.  One query row per head is no work for the MXU, so scores and
  the weighted sum are elementwise products reduced on the VPU with
  ``(Hkv, D)`` kept as the minor dims throughout (Mosaic refuses the
  head-batched ``einsum("hd,jhd->hj")`` this replaced).
- **prefill** (``paged_prefill_attention``): grid ``(S / q_tile,
  max_blocks)``; each tile of the tail bucket's queries attends over the
  slot's whole block row (shared prefix blocks + the freshly written
  tail) in one kernel scope, streaming key blocks with an
  absolute-position causal mask ``kpos <= start + s`` — the fused
  replacement for the gather + two-phase mask of
  ``block_prefill_attention``.  Queries and output are head-major
  ``[H, S, D]`` so each head is a plain 2-D ``[q_tile, D] x [D,
  block_size]`` matmul; the query tile bounds VMEM (the whole 1024
  bucket at once needed 41 MiB against v5e's 16 MiB scoped limit).

GQA stays inside the kernels with no repeat: the wrappers lay queries
out so kv head ``g`` serves query heads ``g * rep .. g * rep + rep - 1``
(consecutive, like the jnp oracle's ``jnp.repeat(k, rep, axis=2)``).

Both kernels run under ``interpret=True`` off-TPU so the CPU tier-1
suite executes the exact kernel code path; shapes depend only on
``(slots, block_size, max_blocks, heads, head_dim)`` — block ids and
lengths are *values*, so the serving engine's zero-recompile discipline
holds unchanged.  All accumulation is f32 (matching the oracle's f32
softmax); parity vs the jnp path is ~1e-6 in interpret mode, asserted in
tests/test_paged_kernel.py.  On the chip f32 operands go through the
MXU at its default (bf16-pass) precision, as the oracle's XLA einsums do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: query rows per prefill grid step (f32 q/out/acc tiles of 16 heads x
#: 128 rows x 64->128 lanes are 1 MiB each; ~7 MiB of VMEM in all)
PREFILL_Q_TILE = 128


def _to_lanes(q, lanes: int):
    """Queries ``[..., D]`` zero-padded to the pool's minor dim: the pad
    lanes add nothing to a score, and the pool's are zero in the output."""
    pad = lanes - q.shape[-1]
    return q if pad == 0 else jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])


# -- decode: one query token per slot, K/V streamed by block table ----------

def _decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, block_size):
    b, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    rep = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    length = len_ref[b]                          # current token index
    # a block is live iff it intersects the valid window 0..length
    # (blocks past the sequence are skipped — their DMA still resolves,
    # to whatever the table row holds, but nothing is accumulated)
    live = i * block_size <= length

    @pl.when(live)
    def _compute():
        k = k_ref[0].astype(jnp.float32)         # [BS, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, k.shape[:2] + (1,), 0)    # [BS, Hkv, 1]
        valid = pos <= length
        for r in range(rep):                     # static: H // Hkv
            q = q_ref[0, r].astype(jnp.float32)  # [Hkv, D]
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, NEG_INF)     # [BS, Hkv, 1]
            m_prev = m_ref[r, :, 0:1]            # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])         # [BS, Hkv, 1]
            corr = jnp.exp(m_prev - m_new)       # [Hkv, 1]
            l_new = l_ref[r, :, 0:1] * corr + jnp.sum(p, axis=0)
            acc_ref[r] = acc_ref[r] * corr + jnp.sum(p * v, axis=0)
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(i == nb - 1)
    def _finalize():
        for r in range(rep):
            l = l_ref[r, :, 0:1]
            l = jnp.where(l == 0.0, 1.0, l)      # unreachable: pos 0 valid
            o_ref[0, r] = (acc_ref[r] / l).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables,
                                  lengths, *, interpret=False):
    """One decode step of attention straight off the block pool.

    Args:
        q:            ``[B, 1, H, D]`` current-token queries.
        k_pool:       ``[num_blocks, block_size, Hkv, Dp]`` one layer of
                      the paged key pool (current token already written);
                      ``Dp >= D``, lanes past ``D`` zero (the serving
                      pool stores whole 128-lane rows).
        v_pool:       same for values.
        block_tables: ``[B, max_blocks]`` int32 block ids per slot.
        lengths:      ``[B]`` int32 current token index per slot
                      (attention window ``0..lengths[b]`` inclusive).

    Returns:
        ``[B, 1, H, D]`` context.  No contiguous K/V copy is ever
        materialized: each grid step reads one pool block by table value.
    """
    B, _, H, head_dim = q.shape
    block_size, Hkv, D = k_pool.shape[1:]
    rep = H // Hkv
    MB = block_tables.shape[1]
    scale = 1.0 / (head_dim ** 0.5)
    q = _to_lanes(q, D)
    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_size=block_size)
    # query head h = g * rep + r  ->  q_g[b, r, g]: kv head g lines up
    # with every one of its rep query heads without an in-kernel repeat
    q_g = q.reshape(B, Hkv, rep, D).transpose(0, 2, 1, 3)
    kv_spec = pl.BlockSpec((1, block_size, Hkv, D),
                           lambda b, i, tbl, lens: (tbl[b, i], 0, 0, 0))
    qo_spec = pl.BlockSpec((1, rep, Hkv, D),
                           lambda b, i, tbl, lens: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((rep, Hkv, D), jnp.float32),
            pltpu.VMEM((rep, Hkv, 128), jnp.float32),
            pltpu.VMEM((rep, Hkv, 128), jnp.float32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rep, Hkv, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q_g, k_pool, v_pool)
    return o_g.transpose(0, 2, 1, 3).reshape(B, 1, H, D)[..., :head_dim]


# -- fused prefill: cached prefix + causal tail in one kernel scope ---------

def _prefill_kernel(row_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale, block_size):
    t, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    Hkv, rep, ts, _ = q_ref.shape

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q0 = start_ref[0] + t * ts                   # tile's first abs position
    # the last live key position is the tile's last query's absolute
    # position; blocks wholly past it contribute nothing (pure prefix
    # blocks below `start` are always live — the fused cross-attention half)
    live = i * block_size <= q0 + ts - 1

    @pl.when(live)
    def _compute():
        # kv heads lead, so each contraction is a head-batched matmul
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)  # [Hkv,BS,D]
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        shape = (Hkv, ts, block_size)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        kpos = i * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        mask = kpos <= qpos                      # abs-position causal mask
        for r in range(rep):                     # static: H // Hkv
            q = q_ref[:, r].astype(jnp.float32)  # [Hkv, ts, D]
            s = jnp.einsum("gqd,gkd->gqk", q, k,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)      # [Hkv, ts, BS]
            m_prev = m_ref[:, r, :, 0:1]         # [Hkv, ts, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            p = jnp.exp(s - m_new)               # [Hkv, ts, BS]
            corr = jnp.exp(m_prev - m_new)       # [Hkv, ts, 1]
            l_new = l_ref[:, r, :, 0:1] * corr + jnp.sum(p, axis=2,
                                                         keepdims=True)
            pv = jnp.einsum("gqk,gkd->gqd", p, v,
                            preferred_element_type=jnp.float32)
            acc_ref[:, r] = acc_ref[:, r] * corr + pv
            m_ref[:, r] = jnp.broadcast_to(m_new, (Hkv, ts, 128))
            l_ref[:, r] = jnp.broadcast_to(l_new, (Hkv, ts, 128))

    @pl.when(i == nb - 1)
    def _finalize():
        l = l_ref[:, :, :, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)


def _q_tile(S: int) -> int:
    """Largest power-of-two tile <= PREFILL_Q_TILE dividing S (prefill
    buckets are powers of two times ``min_bucket``; an S no such tile
    divides runs as one tile)."""
    t = min(PREFILL_Q_TILE, S)
    while t > 8 and S % t:
        t //= 2
    return t if S % t == 0 else S


def paged_prefill_attention_kernel(q, k_pool, v_pool, block_row, start,
                                   *, interpret=False):
    """Fused tail-bucket prefill attention straight off the block pool.

    The tail's S queries (absolute positions ``start..start+S-1``)
    attend over the slot's whole block row — cached prefix blocks and
    the freshly written tail — under one absolute-position causal mask,
    streamed block by block with an online softmax (no gathered
    contiguous K/V copy, no second masking phase).

    Args:
        q:         ``[1, S, H, D]`` tail queries.
        k_pool:    ``[num_blocks, block_size, Hkv, Dp]`` layer key pool
                   (``Dp >= D``, lanes past ``D`` zero).
        v_pool:    same for values.
        block_row: ``[max_blocks]`` int32 — the slot's block-table row.
        start:     ``[1]`` int32 — absolute position of the first query
                   (== cached prefix length, a block boundary).

    Returns:
        ``[1, S, H, D]`` context.
    """
    _, S, H, head_dim = q.shape
    block_size, Hkv, D = k_pool.shape[1:]
    rep = H // Hkv
    MB = block_row.shape[0]
    ts = _q_tile(S)
    scale = 1.0 / (head_dim ** 0.5)
    q = _to_lanes(q, D)
    kernel = functools.partial(_prefill_kernel, scale=scale,
                               block_size=block_size)
    # head-major queries, query head h = g * rep + r  ->  q_g[g, r]
    q_g = q[0].transpose(1, 0, 2).reshape(Hkv, rep, S, D)
    kv_spec = pl.BlockSpec((1, block_size, Hkv, D),
                           lambda t, i, row, st: (row[i], 0, 0, 0))
    qo_spec = pl.BlockSpec((Hkv, rep, ts, D),
                           lambda t, i, row, st: (0, 0, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S // ts, MB),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, rep, ts, D), jnp.float32),
            pltpu.VMEM((Hkv, rep, ts, 128), jnp.float32),
            pltpu.VMEM((Hkv, rep, ts, 128), jnp.float32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, rep, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_row.astype(jnp.int32),
      jnp.asarray(start, dtype=jnp.int32).reshape(1), q_g, k_pool, v_pool)
    return o_g.reshape(H, S, D).transpose(1, 0, 2)[None, ..., :head_dim]

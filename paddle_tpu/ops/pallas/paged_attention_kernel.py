"""Pallas TPU paged attention: block-table-consuming decode + fused
cached-prefix/causal-tail prefill kernels for the paged serving path, both
walking a work list of live chunks.

Reference parity: the jnp formulation in ``ops.cached_attention``
(``gather_block_kv`` + ``cached_attention`` for decode,
``gather_block_kv`` + ``block_prefill_attention`` for tail prefill) —
re-designed flash-decoding style (FlashFuser, arXiv:2512.12949: one
kernel scope over the cached prefix and the causal tail) so the block
table is consumed *inside* the kernel instead of first materializing a
contiguous ``[slots, max_blocks * block_size, Hkv, D]`` copy of every
slot's K/V in HBM:

- **decode** (``paged_decode_attention``): the grid is a *work list*, not
  ``slots x blocks``: one place per (active slot, live chunk), built outside
  the kernel from the lengths and the step's ``active`` mask
  (``mla_attention_kernel.decode_work_list``, the latent kernel's own) and
  handed over in scalar prefetch behind the block table and the lengths.
  A chunk is ``decode_chunk_tokens`` tokens' blocks (256 tokens at GPT-2
  345M's pool; a function of ``block_size``, ``max_blocks``, ``Hkv``, the
  lane width and the dtype under v5e's 16 MiB of scoped VMEM, no knob).  The
  pools stay in HBM (``memory_space=ANY``): a work item copies the chunk's
  *live* blocks into VMEM, one DMA a block and side, selected by the table
  *value* (the automatic-kernel-generation move of arXiv:2006.12645: the
  kernel is not specialized per table), then does one online-softmax update
  per query head over the whole chunk; the accumulators reset at a slot's
  first chunk and its output row is written at its last.  The grid itself is
  **static**, ``slots x max_chunks`` places with the live count ``n`` in
  scalar prefetch: places ``i >= n`` do nothing and keep the last item's
  slot, so their index maps move no row in or out (under 0.1 us each).  A
  dynamic bound would be the custom call's *first* operand on this jax and
  ``paged_decode_roofline`` tells the kernel in a trace by its first two,
  the ``s32[slots, max_blocks]`` table and the ``s32[slots]`` lengths.  Rows
  of slots that are not active are never written; the wrapper zeroes them.
  One query row per head is no work for the MXU, so where a KV head serves
  fewer than :data:`MXU_QUERY_ROWS` (4) query heads — ``rep`` 1 or 2 — scores
  and the weighted sum are elementwise products reduced on the VPU with
  ``(Hkv, D)`` kept as the minor dims throughout (Mosaic refuses the
  head-batched ``einsum("hd,jhd->hj")`` this replaced); from ``rep`` 4 on the
  KV head's query heads are the rows of one matmul a chunk against its keys
  and one against its values (``_grouped_update``: ``rep`` 4 and 8 both; at 4
  the loop measured 6.7 us a chunk and the matmul 3.5).  Keys past a slot's length are
  masked in the scores and *values* past it are dropped before the product
  (a weight of zero does not hide a NaN), so nothing a dead block or the
  tail of the last live one holds reaches an output.
- **prefill** (``paged_prefill_attention``): the same form.  The grid is
  a work list of (query tile, key chunk) items, tile-major and chunks
  ascending, built in the wrapper (``prefill_work_list``) from ``start``, the
  prompt's real ``length`` and ``window``: a tile that holds a real row has
  the chunks from its first row's oldest key (chunk 0 with no window) to its
  last *real* row; a tile of padding has none, is never visited, and its
  output rows are zeros (the wrapper's, with the pad rows of the last real
  tile).  Tile and chunk come from ``prefill_plan``, a function of the shapes
  alone: the chunk is the decode kernel's (256 tokens at the cells' pools),
  the tile the largest power-of-two divisor of the bucket whose float32 score
  tile ``[Hkv, rep * tile, chunk]`` is at most 1 MiB (32 rows at 4 KV heads
  of 8 query heads, 64 at 16 heads of their own), which keeps the kernel
  inside 16 MiB of scoped VMEM up to 32 KV heads of 128.  **The copies are
  off an item's critical path**: the pools stay in HBM, the kernel keeps TWO
  chunk buffers a side, and the grid has one step more than the list has
  items: step ``s`` starts the copies of item ``s`` into buffer ``s % 2`` and
  then awaits and multiplies item ``s - 1`` in the other, whose copies the
  step before started (the first step only fetches, the last only
  multiplies).  One site starts and one awaits, by one rule: an item's tile,
  chunk and live blocks come from its place of the list through
  ``prefill_item_blocks``, so what is awaited is what was started, and no
  copy is outstanding when the list ends.  **A run of the pool comes in one
  copy a side**: ``chunk_runs`` flags, a chunk of the slot's block row,
  whether its table entries are consecutive block ids (a document prefilled
  into a fresh pool lies so), in scalar prefetch; a flagged chunk whose every
  block is live is ONE descriptor a side, any other — a chunk of scattered
  blocks, or one the tile's last real row or its first row's window cuts —
  one a live block by table value, none past the tile's last real row, none
  wholly behind its first row's
  window (a released or stale table entry is never followed).  The decision
  reads the table and the lengths, nothing else.  Queries reach the kernel as
  ``[Hkv, tiles * rep * tile, D]`` in the pool's dtype, so a KV head's ``rep
  * tile`` query rows are the rows of ONE matmul against the chunk's keys and
  of one against its values (``precision=DEFAULT``, float32 statistics and
  accumulators); ``rep`` 1 is the same contraction with fewer rows.  The
  absolute-position causal mask ``kpos <= qpos`` (and ``kpos > qpos -
  window``) is the fused replacement for the gather + two-phase mask of
  ``block_prefill_attention``; values no real row of the tile may read are
  dropped before the product, whatever a copy brought.  Given ``scores`` and
  ``cut`` (``_prefill_call``; ``dsa_attention_kernel.sparse_prefill`` gives
  them) the mask has one more condition, ``scores >= cut``, the item's ``[tile,
  chunk]`` tile of the scores read beside the queries and repeated over a KV
  head's query heads: the indexed model's tail prefill is this kernel, under
  the name ``dsa_sparse_prefill``.  The grid's bound is the list's live count
  and one (an idle place past it cost 0.05 us, and a 16-token tail's 121
  items in a 256-row bucket had 903 of them behind: 6 % of the call); the
  list's arrays are static (``prefill_places``: tiles x the row's chunks, or
  x a window's).  No reader of this kernel tells it by its operands' order
  (``swa_paged_prefill.PATTERNS`` is its name).  The entry is jitted on its
  static arguments, so a model's layers trace and lower it once a (shape,
  window).

GQA stays inside the kernels with no repeat: the wrappers lay queries
out so kv head ``g`` serves query heads ``g * rep .. g * rep + rep - 1``
(consecutive, like the jnp oracle's ``jnp.repeat(k, rep, axis=2)``).

Both kernels run under ``interpret=True`` off-TPU so the CPU tier-1
suite executes the exact kernel code path; shapes depend only on
``(slots, block_size, max_blocks, heads, head_dim)`` and the bucket — block
ids, lengths, ``start``, the active mask and the work lists are *values*, so
the serving engine's zero-recompile discipline holds unchanged.  All
accumulation is f32 (matching the oracle's f32 softmax); parity vs the jnp
path is ~1e-6 in interpret mode on a float32 pool, asserted in
tests/test_paged_kernel.py and tests/test_swa_kernels.py.  On the chip the
MXU's operands are the pool's dtype (bf16) at its default precision, as the
oracle's XLA einsums are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_attention_kernel import decode_work_list

NEG_INF = -1e30

#: the decode kernel's chunk: at most this many tokens a work item ...
DECODE_CHUNK_TOKENS = 256
#: ... and buffers of at most this much of v5e's 16 MiB of scoped VMEM (the
#: rest is the compiler's own and the small per-head arrays)
DECODE_VMEM_BYTES = 12 << 20
#: float32 arrays of a chunk's shape ``[tokens, kv_heads, lanes]`` the kernel
#: body holds at once: keys, values, and the two products
DECODE_F32_CHUNKS = 4

#: query heads a KV head must serve for the decode kernel to take them as the
#: rows of a matmul; fewer are multiplied a row at a time on the VPU (one row
#: is no work for the MXU).  By ``rep``, the query heads a KV head: **1** (16
#: heads of their own) and 2 take the VPU loop; **4** (32 query / 8 KV heads
#: of 64 in 128 lanes) and **8** (32 / 4 of 128) the matmul form — at 4 the
#: loop costs 6.7 us a 256-key chunk and the matmul 3.5 (64 slots of 8,500
#: keys: 14.6 against 7.6 ms a layer, PERF.md section 6, PR 40), at 8 it was
#: 17 us (PR 38); 2 has not been timed
MXU_QUERY_ROWS = 4

#: query rows a prefill work item holds at most ...
PREFILL_Q_TILE = 128
#: ... and as many of them as keep the item's float32 score tile ``[kv_heads,
#: rows a KV head, chunk]`` (and the weights of its shape) this small: with
#: the query, output and accumulator tiles and the chunk's forms the kernel
#: stays inside v5e's 16 MiB of scoped VMEM
PREFILL_SCORE_BYTES = 1 << 20


def _to_lanes(q, lanes: int):
    """Queries ``[..., D]`` zero-padded to the pool's minor dim: the pad
    lanes add nothing to a score, and the pool's are zero in the output."""
    pad = lanes - q.shape[-1]
    return q if pad == 0 else jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])


# -- decode: one query token per slot, K/V copied by block table ------------

def decode_chunk_tokens(block_size: int, max_blocks: int, kv_heads: int,
                        lanes: int, itemsize: int) -> int:
    """Tokens one work item of the decode kernel attends over: whole blocks,
    as many as the chunk's buffers (K and V in the pool's dtype, and
    :data:`DECODE_F32_CHUNKS` float32 arrays of the chunk's shape, each
    token's ``(kv_heads, lanes)`` in whole sublane tiles) fit in
    :data:`DECODE_VMEM_BYTES`; at most :data:`DECODE_CHUNK_TOKENS` and the
    slot's whole row, at least one block.  A function of the shapes alone:
    the engine's host counts its ``decode_chunks`` with it."""
    tile = 32 // itemsize                       # rows of a sublane tile
    pool_rows = -(-kv_heads // tile) * tile
    f32_rows = -(-kv_heads // 8) * 8
    per_token = lanes * (2 * pool_rows * itemsize
                         + DECODE_F32_CHUNKS * f32_rows * 4)
    tokens = min(DECODE_CHUNK_TOKENS, DECODE_VMEM_BYTES // per_token)
    return max(1, min(tokens // block_size, max_blocks)) * block_size


def _decode_kernel(tbl_ref, len_ref, slot_ref, chunk_ref, n_ref, q_ref,
                   k_hbm, v_hbm, o_ref, k_ref, v_ref, sem, acc_ref, m_ref,
                   l_ref, *, scale, cb, bs, mb, window):
    i = pl.program_id(0)
    rep = q_ref.shape[1]
    ct = cb * bs

    @pl.when(i < n_ref[0])                       # places past the list: idle
    def _item():
        b, c = slot_ref[i], chunk_ref[i]
        length = len_ref[b]                      # keys lo..length inclusive
        # a window layer's query reads the ``window`` keys up to its own
        lo = jnp.maximum(length - (window - 1), 0) if window else 0

        @pl.when(c == lo // ct)                  # the slot's first chunk
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # one copy a live block of the chunk and side, by table value; the
        # blocks past the slot's length are not read (what the buffers hold
        # there is masked below).  All are started, then all awaited; the
        # loops are the device's, so a chunk of many blocks traces as one.
        def block_copies(j):
            blk = tbl_ref[b, jnp.minimum(c * cb + j, mb - 1)]
            row = pl.ds(pl.multiple_of(j * bs, bs), bs)
            return [pltpu.make_async_copy(pool.at[blk], buf.at[row],
                                          sem.at[side, j])
                    for side, (pool, buf) in enumerate(((k_hbm, k_ref),
                                                        (v_hbm, v_ref)))]

        def start(j, carry):
            for cp in block_copies(j):
                cp.start()
            return carry

        def wait(j, carry):
            for cp in block_copies(j):
                cp.wait()
            return carry

        live = jnp.minimum(length // bs - c * cb + 1, cb)
        head = jnp.maximum(lo // bs - c * cb, 0)  # blocks behind the window
        jax.lax.fori_loop(head, live, start, 0)
        jax.lax.fori_loop(head, live, wait, 0)

        if rep >= MXU_QUERY_ROWS:
            _grouped_update(c, ct, length, lo, q_ref, k_ref, v_ref, o_ref,
                            acc_ref, m_ref, l_ref, scale)
            return
        k = k_ref[...].astype(jnp.float32)       # [ct, Hkv, D]
        pos = c * ct + jax.lax.broadcasted_iota(
            jnp.int32, k.shape[:2] + (1,), 0)    # [ct, Hkv, 1]
        valid = (pos <= length) & (pos >= lo)
        # a weight of zero does not hide a NaN: masked values are dropped
        v = jnp.where(valid, v_ref[...].astype(jnp.float32), 0.0)
        for r in range(rep):                     # static: H // Hkv
            q = q_ref[0, r].astype(jnp.float32)  # [Hkv, D]
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, NEG_INF)     # [ct, Hkv, 1]
            m_prev = m_ref[r, :, 0:1]            # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])         # [ct, Hkv, 1]
            corr = jnp.exp(m_prev - m_new)       # [Hkv, 1]
            l_new = l_ref[r, :, 0:1] * corr + jnp.sum(p, axis=0)
            acc_ref[r] = acc_ref[r] * corr + jnp.sum(p * v, axis=0)
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        @pl.when(c == length // ct)              # the slot's last live chunk
        def _finalize():
            for r in range(rep):                 # l > 0: position c*ct valid
                o_ref[0, r] = (acc_ref[r] / l_ref[r, :, 0:1]
                               ).astype(o_ref.dtype)


def _grouped_update(c, ct, length, lo, q_ref, k_ref, v_ref, o_ref, acc_ref,
                    m_ref, l_ref, scale):
    """A work item's online-softmax update where a KV head serves
    :data:`MXU_QUERY_ROWS` query heads or more (grouped queries: 8 of them at
    32 / 4, 4 at 32 / 8): the
    ``rep`` query rows of a KV head are the rows of ONE matmul against the
    chunk's keys, and of one against its values, KV heads leading as in the
    prefill kernel — where the loop below multiplies the chunk by one query
    row at a time on the VPU, ``rep`` times over (17 us a 256-key chunk at
    ``rep`` 8 on the chip, PERF.md section 6).  Scratch is ``[Hkv, rep, .]``
    here."""
    op = k_ref.dtype                             # the MXU's operands
    k = jnp.swapaxes(k_ref[...].astype(jnp.float32), 0, 1)    # [Hkv, ct, D]
    v = jnp.swapaxes(v_ref[...].astype(jnp.float32), 0, 1)
    pos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (1, ct, 1), 1)
    # a weight of zero does not hide a NaN: masked values are dropped
    v = jnp.where((pos <= length) & (pos >= lo), v, 0.0)
    q = jnp.swapaxes(q_ref[0], 0, 1)             # [Hkv, rep, D]
    s = jnp.einsum("grd,gkd->grk", q.astype(op), k.astype(op),
                   precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.float32) * scale
    kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ct), 2)
    s = jnp.where((kpos <= length) & (kpos >= lo), s, NEG_INF)
    m_prev = m_ref[:, :, 0:1]                    # [Hkv, rep, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    p = jnp.exp(s - m_new)                       # [Hkv, rep, ct]
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, :, 0:1] * corr + jnp.sum(p, axis=2, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
        "grk,gkd->grd", p.astype(op), v.astype(op),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(c == length // ct)                  # the slot's last live chunk
    def _finalize():                             # l > 0: position c*ct valid
        o_ref[0] = jnp.swapaxes(acc_ref[...] / l_ref[:, :, 0:1], 0, 1
                                ).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, block_tables,
                                  lengths, active, *, window=0,
                                  interpret=False):
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
        active:       ``[B]`` int32, nonzero for the running slots.
        window:       0, or the keys a query reads: its own position and
                      the ``window - 1`` before it.  The work list then holds
                      only the chunks that meet ``[lengths[b] - window + 1,
                      lengths[b]]``, the chunk at the lower edge is masked,
                      and no block wholly behind it is copied (its table
                      entry may have been released).

    Returns:
        ``[B, 1, H, D]`` context; zero for slots that are not active.  No
        contiguous K/V copy is ever materialized: a work item copies its
        chunk's live blocks by table value.
    """
    bs, Hkv, D = k_pool.shape[1:]
    ct = decode_chunk_tokens(bs, block_tables.shape[1], Hkv, D,
                             k_pool.dtype.itemsize)
    return _decode_call(q, k_pool, v_pool, block_tables, lengths, active,
                        first_chunks(lengths, int(window), ct),
                        chunk_tokens=ct, window=int(window),
                        interpret=interpret)


def first_chunks(lengths, window: int, chunk_tokens: int):
    """The first chunk a slot's work list holds under ``window`` (None with
    none: chunk 0): the one of its query's oldest key."""
    if not window:
        return None
    return jnp.maximum(lengths.astype(jnp.int32) - (window - 1), 0) \
        // chunk_tokens


# jitted so that a model's layers, and the passes a program is traced in,
# trace and lower the kernel once for their shapes and not once each
@functools.partial(jax.jit, static_argnames=("chunk_tokens", "window",
                                             "interpret"))
def _decode_call(q, k_pool, v_pool, block_tables, lengths, active, first, *,
                 chunk_tokens, window, interpret):
    B, _, H, head_dim = q.shape
    bs, Hkv, D = k_pool.shape[1:]
    rep = H // Hkv
    mb = block_tables.shape[1]
    ct, cb = chunk_tokens, chunk_tokens // bs
    max_chunks = -(-mb // cb)
    scale = 1.0 / (head_dim ** 0.5)
    q = _to_lanes(q, D)
    lengths = lengths.astype(jnp.int32)
    slot, chunk, n = decode_work_list(lengths, active, ct, max_chunks, first)
    # a place past the list keeps the last item's slot: its index maps
    # move no query row in and, above all, no output row out
    slot = jnp.where(jnp.arange(slot.shape[0]) < n, slot,
                     slot[jnp.maximum(n - 1, 0)])
    kernel = functools.partial(_decode_kernel, scale=scale, cb=cb, bs=bs,
                               mb=mb, window=window)
    # query head h = g * rep + r  ->  q_g[b, r, g]: kv head g lines up
    # with every one of its rep query heads without an in-kernel repeat
    q_g = q.reshape(B, Hkv, rep, D).transpose(0, 2, 1, 3)
    qo_spec = pl.BlockSpec((1, rep, Hkv, D),
                           lambda i, tbl, lens, sl, ch, n: (sl[i], 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    # the accumulators' leading dims: KV heads first where they batch matmuls
    lead = (Hkv, rep) if rep >= MXU_QUERY_ROWS else (rep, Hkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B * max_chunks,),                  # static: see the docstring
        in_specs=[qo_spec, pool_spec, pool_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((ct, Hkv, D), k_pool.dtype),
            pltpu.VMEM((ct, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, cb)),
            pltpu.VMEM(lead + (D,), jnp.float32),
            pltpu.VMEM(lead + (128,), jnp.float32),
            pltpu.VMEM(lead + (128,), jnp.float32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rep, Hkv, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), lengths, slot, chunk, n.reshape(1),
      q_g, k_pool, v_pool)
    out = o_g.transpose(0, 2, 1, 3).reshape(B, 1, H, D)[..., :head_dim]
    return jnp.where((active > 0)[:, None, None, None], out, 0)


# -- prefill write: whole blocks into a layer buffer, in place ----------------

def _write_blocks_kernel(ids_ref, upd_hbm, _pool_in, pool_out, sem):
    def copy(j):
        return pltpu.make_async_copy(upd_hbm.at[j], pool_out.at[ids_ref[j]],
                                     sem.at[0])

    def start(j, carry):
        copy(j).start()
        return carry

    def wait(j, carry):
        copy(j).wait()
        return carry

    n = upd_hbm.shape[0]
    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)


def blocks_need_kernel_write(kv_heads: int, itemsize: int) -> bool:
    """Whether a tail bucket's blocks are written into a layer buffer
    ``[num_blocks, block_size, kv_heads, lanes]`` by :func:`write_blocks`
    rather than by an XLA scatter.  Where ``kv_heads`` fills whole sublane
    tiles (16 rows of bfloat16, 8 of float32), or is 1, XLA:TPU scatters
    into the buffer as the kernels read it.  For a few heads (4 or 8 KV heads
    under grouped queries) it gives its scatter a layout with the *block's
    tokens* on the sublanes and converts the whole buffer there and back in
    every prefill program (two copies of a layer buffer a side a layer:
    seen in the program compiled for a v5e)."""
    return kv_heads > 1 and kv_heads % (32 // itemsize) != 0


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_blocks(pool, upd, block_ids, *, interpret=False):
    """``pool`` with its blocks ``block_ids [n]`` overwritten by ``upd [n,
    block_size, kv_heads, lanes]``, in place (the result aliases ``pool``):
    one DMA a block from ``upd`` to where the table says, the buffer in the
    kernels' own row-major form on both sides of the call."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        _write_blocks_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kv_block_write",
    )(block_ids.astype(jnp.int32), upd.astype(pool.dtype), pool)


# -- fused prefill: cached prefix + causal tail in one kernel scope ---------

def prefill_plan(S: int, kv_heads: int, rep: int, lanes: int, itemsize: int,
                 block_size: int, max_blocks: int):
    """``(tile, chunk_tokens)`` of the tail-prefill kernel: the query rows a
    work item multiplies and the keys it multiplies them against, from the
    shapes alone (the engine's host counts its ``prefill_items_*`` with it and
    :func:`prefill_tile_chunks`).

    The chunk is the decode kernel's (:func:`decode_chunk_tokens`: whole
    blocks, 256 tokens where its buffers fit).  The tile is the largest
    power-of-two divisor of ``S`` up to :data:`PREFILL_Q_TILE` whose float32
    score tile ``[kv_heads, rep * tile, chunk]`` stays within
    :data:`PREFILL_SCORE_BYTES` (an ``S`` no such tile divides runs as one
    tile): 32 rows at 4 KV heads of 8 query heads each, 64 at 16 heads of
    their own."""
    ct = decode_chunk_tokens(block_size, max_blocks, kv_heads, lanes,
                             itemsize)
    t = min(PREFILL_Q_TILE, S)
    while t > 8 and (S % t or kv_heads * rep * t * ct * 4
                     > PREFILL_SCORE_BYTES):
        t //= 2
    return (t if S % t == 0 else S), ct


def prefill_places(S: int, tile: int, chunk_tokens: int, max_blocks: int,
                   block_size: int, window: int) -> int:
    """The static size of the prefill kernel's work list (its grid's bound
    is the live count and one): a query tile's chunks are the slot's whole
    row at most, and with a window those from its first row's oldest key to
    its last row."""
    chunks = -(-max_blocks * block_size // chunk_tokens)
    if window:
        chunks = min(chunks, (window + tile - 2) // chunk_tokens + 2)
    return S // tile * chunks


def prefill_tile_chunks(start, length, *, S: int, tile: int,
                        chunk_tokens: int, window: int, xp=jnp):
    """``(first, count)`` by query tile of the tail ``[start, length)`` in a
    bucket of ``S`` rows: a tile with no real row has no chunk; a tile that
    has one has those from its first row's oldest key (chunk 0 with no
    window) to its last *real* row.  With ``xp=numpy`` the host counts what
    the kernel will do by the same rule (``count.sum()`` work items)."""
    q0 = start + xp.arange(S // tile, dtype=xp.int32) * tile
    last = xp.minimum(q0 + tile, length) - 1      # the tile's last real row
    first = (xp.maximum(q0 - (window - 1), 0) // chunk_tokens if window
             else xp.zeros_like(q0))
    return first, xp.where(q0 < length, last // chunk_tokens - first + 1, 0)


def prefill_work_list(start, length, *, S: int, tile: int, chunk_tokens: int,
                      window: int, places: int, xp=jnp):
    """``(tile, chunk, n)``: the (query tile, key chunk) pairs of
    :func:`prefill_tile_chunks`, tile-major and chunks ascending, in the
    first ``n`` of ``places`` places.  With ``xp=numpy`` the host walks the
    kernel's own list."""
    first, per = prefill_tile_chunks(start, length, S=S, tile=tile,
                                     chunk_tokens=chunk_tokens, window=window,
                                     xp=xp)
    ends = xp.cumsum(per)
    n = ends[-1]
    idx = xp.arange(places, dtype=xp.int32)
    t = xp.minimum(xp.searchsorted(ends, idx, side="right"), S // tile - 1)
    chunk = idx - (ends - per)[t] + first[t]
    # the grid's bound is ``n + 1``: no step reads a place past the list
    # (they keep the last item's tile)
    t = xp.where(idx < n, t, t[xp.maximum(n - 1, 0)])
    return (t.astype(xp.int32), xp.where(idx < n, chunk, 0).astype(
        xp.int32), n.astype(xp.int32))


def chunk_runs(tables, chunk_blocks: int, xp=jnp):
    """``[..., chunks]`` bool of block tables ``[..., max_blocks]``: where a
    chunk's ``chunk_blocks`` entries are consecutive block ids, so that the
    chunk is one run of the pool and comes in one copy (a document prefilled
    into a fresh pool lies so).  A last chunk the row does not fill is none.
    With ``xp=numpy`` the host counts by the same rule."""
    mb = tables.shape[-1]
    pad = -mb % chunk_blocks
    t = xp.pad(tables, [(0, 0)] * (tables.ndim - 1) + [(0, pad)],
               constant_values=-1)
    t = t.reshape(tables.shape[:-1] + ((mb + pad) // chunk_blocks,
                                       chunk_blocks))
    return xp.all(t[..., 1:] == t[..., :-1] + 1, axis=-1)


def prefill_item_blocks(t, c, start, length, *, tile: int, block_size: int,
                        chunk_blocks: int, window: int, xp=jnp):
    """``(q0, hi, lo, head, live)`` of the work item (query tile ``t``, key
    chunk ``c``): the tile's first position, the keys ``lo .. hi`` its real
    rows may read (up to its last real row, from its first row's oldest key)
    and the chunk's blocks ``head .. live - 1`` that hold one of them — the
    blocks an item copies.  The kernel asks it of the item it multiplies and
    of the one it fetches for; with ``xp=numpy`` the host counts by it."""
    q0 = start + t * tile
    hi = xp.minimum(q0 + tile, length) - 1
    lo = xp.maximum(q0 - (window - 1), 0) if window else xp.zeros_like(q0)
    live = xp.minimum(hi // block_size - c * chunk_blocks + 1, chunk_blocks)
    head = xp.maximum(lo // block_size - c * chunk_blocks, 0)
    return q0, hi, lo, head, live


def prefill_one_copy(run, head, live, chunk_blocks: int):
    """Whether an item's chunk comes in ONE copy a side: its blocks are a run
    of the pool (``run``, :func:`chunk_runs`) and every one of them is live
    (:func:`prefill_item_blocks`), so that neither the tile's last real row
    nor its window cuts it.  The kernel's rule, and the host's count."""
    return run & (head == 0) & (live == chunk_blocks)


def prefill_item_counts(block_row, start, length, *, S: int, tile: int,
                        chunk_tokens: int, block_size: int, window: int):
    """``(items, run_items)`` of the tail ``[start, length)``: the work items
    the kernel walks, and those of them whose chunk comes in ONE copy a side,
    from ``block_row`` (numpy, the slot's table row as the host has it): the
    kernel's own list (:func:`prefill_work_list`) and rule
    (:func:`prefill_one_copy`), on the host."""
    import numpy as np

    cb = chunk_tokens // block_size
    t, c, n = prefill_work_list(
        np.int32(start), np.int32(length), S=S, tile=tile,
        chunk_tokens=chunk_tokens, window=window, xp=np,
        places=prefill_places(S, tile, chunk_tokens, len(block_row),
                              block_size, window))
    t, c = t[:n], c[:n]
    _, _, _, head, live = prefill_item_blocks(
        t, c, start, length, tile=tile, block_size=block_size,
        chunk_blocks=cb, window=window, xp=np)
    runs = chunk_runs(np.asarray(block_row), cb, xp=np)
    return int(n), int(np.sum(prefill_one_copy(runs[c], head, live, cb)))


def _prefill_kernel(row_ref, start_ref, len_ref, tile_ref, chunk_ref, run_ref,
                    q_ref, *refs, scale, bs, mb, ts, window, selected):
    if selected:                                 # causal AND scores >= cut
        sc_ref, cut_ref, *refs = refs
    k_hbm, v_hbm, o_ref, k_ref, v_ref, sem, acc_ref, m_ref, l_ref = refs
    step = pl.program_id(0)
    n = pl.num_programs(0) - 1                   # a step more than items
    cb = k_ref.shape[1]
    ct = cb * bs
    rows = q_ref.shape[1]                        # rep * ts: row r * ts + j

    def item(at):
        t, c = tile_ref[at], chunk_ref[at]
        return (c,) + prefill_item_blocks(
            t, c, start_ref[0], len_ref[0], tile=ts, block_size=bs,
            chunk_blocks=cb, window=window)

    def copies(it, buf, go):
        """Start (``go``) or await the copies of the chunk of ``it`` (an
        :func:`item`) into buffer ``buf``, by the one rule both sides of a
        copy read: the whole chunk in ONE copy a side where its blocks are a
        run of the pool and all live, else one a live block, by table value —
        none past the tile's last real row, none wholly behind its first
        row's window (a released entry is never followed; what a buffer holds
        there is dropped below).  An await needs a copy's size and semaphore,
        not its source: it reads no table."""
        c, _q0, _hi, _lo, head, live = it
        sides = tuple(enumerate(((k_hbm, k_ref), (v_hbm, v_ref))))
        whole = prefill_one_copy(run_ref[c] == 1, head, live, cb)
        if k_hbm.shape[0] < cb:
            # a pool smaller than a chunk (a window group sized for one short
            # sequence) holds no run of one, and no slice of one can be taken
            whole = False

        @pl.when(whole)
        def _one_copy():
            first = row_ref[c * cb] if go else 0
            for side, (pool, dst) in sides:
                cp = pltpu.make_async_copy(pool.at[pl.ds(first, cb)],
                                           dst.at[buf], sem.at[buf, side, 0])
                (cp.start if go else cp.wait)()

        @pl.when(jnp.logical_not(whole))
        def _a_copy_a_block():
            def block(j, carry):
                blk = row_ref[jnp.minimum(c * cb + j, mb - 1)] if go else 0
                for side, (pool, dst) in sides:
                    cp = pltpu.make_async_copy(pool.at[blk], dst.at[buf, j],
                                               sem.at[buf, side, j])
                    (cp.start if go else cp.wait)()
                return carry

            jax.lax.fori_loop(head, live, block, 0)

    # one step more than the list has items: step ``s`` starts the copies of
    # item ``s`` into buffer ``s % 2`` (the last step has none to start), then
    # awaits and multiplies item ``s - 1`` in the other — whose copies the
    # step before started while the item before multiplied (the first step
    # only fetches).  ONE site starts and one awaits, by the same rule, and no
    # copy is outstanding when the list ends.
    @pl.when(step < n)
    def _fetch():
        copies(item(step), step % 2, True)

    @pl.when(step > 0)
    def _item():
        i = step - 1
        cur, buf = item(i), i % 2
        copies(cur, buf, False)
        c, q0, hi, lo, _head, _live = cur

        @pl.when(c == lo // ct)                      # the tile's first chunk
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        op = k_ref.dtype                             # the MXU's operands
        # kv heads lead: a KV head's rep * ts query rows are the rows of ONE
        # matmul against the chunk's keys, and of one against its values
        k = jnp.swapaxes(k_ref[buf].reshape((ct,) + k_ref.shape[3:]
                                            ).astype(jnp.float32), 0, 1)
        v = jnp.swapaxes(v_ref[buf].reshape((ct,) + v_ref.shape[3:]
                                            ).astype(jnp.float32), 0, 1)
        pos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (1, ct, 1), 1)
        # a weight of zero does not hide a NaN: values no real row of the tile
        # may read (past the prompt, or in a block not copied) go
        v = jnp.where((pos <= hi) & (pos >= lo), v, 0.0)
        s = jnp.einsum("grd,gkd->grk", q_ref[...], k.astype(op),
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32) * scale
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 0)
        j = r & (ts - 1) if ts & (ts - 1) == 0 else r % ts
        # a pad row of the tile reads what the last real row reads
        qpos = jnp.minimum(q0 + j, hi)
        kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 1)
        mask = kpos <= qpos                          # abs-position causal mask
        if window:
            mask &= kpos > qpos - window
        if selected:
            # the selection of the tile's ts queries, the same for a query's
            # rep heads: rep copies of one sublane-aligned float32 tile (no
            # vector of bits is repeated)
            sel = jnp.where(sc_ref[...] >= cut_ref[...], 1.0, 0.0)
            mask &= jnp.concatenate([sel] * (rows // ts), axis=0) > 0.5
        s = jnp.where(mask[None], s, NEG_INF)        # [Hkv, rows, ct]
        m_prev = m_ref[...]                          # [Hkv, rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # a row that has met no key of its own yet keeps nothing
        p = jnp.where(mask[None], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "grk,gkd->grd", p.astype(op), v.astype(op),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

        @pl.when(c == hi // ct)                      # the tile's last chunk
        def _finalize():
            l = l_ref[...]                       # > 0: a row reads itself
            if selected:                         # but a pad row's selection
                l = jnp.where(l == 0.0, 1.0, l)  # may hold no key at all
            o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_kernel(q, k_pool, v_pool, block_row, start,
                                   length=None, *, window=0, interpret=False):
    """Fused tail-bucket prefill attention straight off the block pool.

    The tail's real queries (absolute positions ``start..length-1``, the
    first rows of the bucket's ``S``) attend over the slot's block row —
    cached prefix blocks and the freshly written tail — under one
    absolute-position causal mask, a work item a (query tile, key chunk)
    pair with an online softmax (no gathered contiguous K/V copy, no second
    masking phase).

    Args:
        q:         ``[1, S, H, D]`` tail queries.
        k_pool:    ``[num_blocks, block_size, Hkv, Dp]`` layer key pool
                   (``Dp >= D``, lanes past ``D`` zero).
        v_pool:    same for values.
        block_row: ``[max_blocks]`` int32 — the slot's block-table row.
        start:     int32 scalar or ``[1]`` — absolute position of the first
                   query (== cached prefix length, a block boundary).
        length:    int32 scalar — the prompt's real length so far: rows
                   ``length - start ..`` of the bucket are padding.  None:
                   every row is real.
        window:    0, or the keys a query reads: ``j <= i`` AND ``j > i -
                   window``.  A query tile's items then cover only the
                   chunks from its first query's oldest key to its last real
                   query, and no block wholly behind its first query's
                   window is copied — its table entry may have been
                   released.

    Returns:
        ``[1, S, H, D]`` context; the pad rows are zeros (a query tile with
        no real row is never visited, whatever the pool holds past the
        prompt).
    """
    S, H = q.shape[1:3]
    bs, Hkv, D = k_pool.shape[1:]
    ts, ct = prefill_plan(S, Hkv, H // Hkv, D, k_pool.dtype.itemsize, bs,
                          block_row.shape[0])
    start = jnp.asarray(start, jnp.int32).reshape(())
    return _prefill_call(
        q, k_pool, v_pool, block_row, start,
        start + S if length is None else jnp.asarray(
            length, jnp.int32).reshape(()),
        tile=ts, chunk_tokens=ct, window=int(window), interpret=interpret)


# jitted like the decode kernel's entry: a model's layers trace and lower the
# kernel once a (shape, window), not once each
@functools.partial(jax.jit, static_argnames=(
    "tile", "chunk_tokens", "window", "interpret", "scale"))
def _prefill_call(q, k_pool, v_pool, block_row, start, length, *, tile,
                  chunk_tokens, window, interpret, scores=None, cut=None,
                  scale=None):
    """The tail-prefill kernel on ``tile`` query rows and ``chunk_tokens``
    keys an item.  ``scores [S, T]`` and ``cut [S, 1]`` (float32) put one
    more condition in the mask: query ``s`` reads key ``t`` only where
    ``scores[s, t] >= cut[s]`` (the indexed model's selection; the kernel's
    name is then ``dsa_sparse_prefill``); ``scale`` is the softmax scale
    where it is not the queries' width ``** -0.5``."""
    _, S, H, head_dim = q.shape
    bs, Hkv, D = k_pool.shape[1:]
    rep = H // Hkv
    mb = block_row.shape[0]
    ts, ct = tile, chunk_tokens
    tiles = S // ts
    selected = scores is not None
    places = prefill_places(S, ts, ct, mb, bs, window)
    t, chunk, n = prefill_work_list(start, length, S=S, tile=ts,
                                    chunk_tokens=ct, window=window,
                                    places=places)
    block_row = block_row.astype(jnp.int32)
    kernel = functools.partial(
        _prefill_kernel, bs=bs, mb=mb, ts=ts, window=window,
        selected=selected,
        scale=1.0 / (head_dim ** 0.5) if scale is None else scale)
    # query head h = g * rep + r, tile t, row j  ->  q_g[g, t, r * ts + j]:
    # a tile's block is the rows of one matmul a KV head, no in-kernel repeat
    q_g = _to_lanes(q[0], D).astype(k_pool.dtype).reshape(
        tiles, ts, Hkv, rep, D).transpose(2, 0, 3, 1, 4).reshape(
        Hkv, tiles * rep * ts, D)
    # (a step multiplies the item before its own place; the first, none)
    qo_spec = pl.BlockSpec(
        (Hkv, rep * ts, D),
        lambda s, row, st, ln, tl, ch, ru: (0, tl[jnp.maximum(s - 1, 0)], 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    selection = []
    if selected:
        # the item's tile of the scores and its queries' cuts, beside them
        pad = -scores.shape[1] % ct
        selection = [jnp.pad(scores.astype(jnp.float32), ((0, 0), (0, pad))),
                     cut.astype(jnp.float32)]
        sel_specs = [
            pl.BlockSpec((ts, ct), lambda s, row, st, ln, tl, ch, ru:
                         (tl[jnp.maximum(s - 1, 0)],
                          ch[jnp.maximum(s - 1, 0)])),
            pl.BlockSpec((ts, 1), lambda s, row, st, ln, tl, ch, ru:
                         (tl[jnp.maximum(s - 1, 0)], 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n + 1,),                           # an item a step, and one
        in_specs=[qo_spec] + (sel_specs if selected else [])
        + [pool_spec, pool_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            # two buffers a side: an item multiplies one while the next
            # item's chunk arrives in the other
            pltpu.VMEM((2, ct // bs, bs, Hkv, D), k_pool.dtype),
            pltpu.VMEM((2, ct // bs, bs, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2, ct // bs)),
            pltpu.VMEM((Hkv, rep * ts, D), jnp.float32),
            # the softmax's running maximum and sum, one number a row: read
            # and written whole every item (128 lanes of it cost an item of
            # 512 rows 0.4 us on the chip)
            pltpu.VMEM((Hkv, rep * ts, 1), jnp.float32),
            pltpu.VMEM((Hkv, rep * ts, 1), jnp.float32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_g.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_sparse_prefill" if selected else "paged_prefill_attention",
    )(block_row, start.reshape(1), length.reshape(1), t, chunk,
      chunk_runs(block_row, ct // bs).astype(jnp.int32), q_g, *selection,
      k_pool, v_pool)
    out = o_g.reshape(Hkv, tiles, rep, ts, D).transpose(1, 3, 0, 2, 4).reshape(
        S, H, D)[..., :head_dim]
    # the tiles never visited, and the pad rows of the last one that was
    return jnp.where((jnp.arange(S) < length - start)[:, None, None], out,
                     0)[None]

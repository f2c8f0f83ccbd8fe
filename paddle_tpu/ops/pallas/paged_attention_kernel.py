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
  alone: the chunk is the decode kernel's (256 tokens at both cells'
  pools), the tile the largest power-of-two divisor of the bucket whose
  float32 score tile ``[Hkv, rep * tile, chunk]`` is at most 1 MiB (32 rows
  at 4 KV heads of 8 query heads, 64 at 16 heads of their own), which keeps
  the kernel inside 16 MiB of scoped VMEM up to 32 KV heads of 128.  The
  pools stay in HBM; an item copies its chunk's blocks by table value, one
  DMA a block and side, all started then all awaited in two ``fori_loop``s —
  none past the tile's last real row, none wholly behind its first row's
  window (a released table entry is never followed).  Queries reach the
  kernel as ``[Hkv, tiles * rep * tile, D]`` in the pool's dtype, so a KV
  head's ``rep * tile`` query rows are the rows of ONE matmul against the
  chunk's keys and of one against its values (``precision=DEFAULT``, float32
  statistics and accumulators); ``rep`` 1 is the same contraction with
  fewer rows.  The absolute-position causal mask ``kpos <= qpos`` (and
  ``kpos > qpos - window``) is the fused replacement for the gather +
  two-phase mask of ``block_prefill_attention``; values no real row of the
  tile may read are dropped before the product.  The grid is static
  (``prefill_places``: tiles x the row's chunks, or x a window's), the live
  count rides in scalar prefetch, and a place past it keeps the last item's
  tile.  The entry is jitted on its static arguments, so a model's layers
  trace and lower it once a (shape, window).

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
    """The static size of the prefill kernel's grid: a query tile's chunks
    are the slot's whole row at most, and with a window those from its first
    row's oldest key to its last row."""
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
                      window: int, places: int):
    """``(tile, chunk, n)``: the (query tile, key chunk) pairs of
    :func:`prefill_tile_chunks`, tile-major and chunks ascending, in the
    first ``n`` of ``places`` places."""
    first, per = prefill_tile_chunks(start, length, S=S, tile=tile,
                                     chunk_tokens=chunk_tokens, window=window)
    ends = jnp.cumsum(per)
    n = ends[-1]
    idx = jnp.arange(places, dtype=jnp.int32)
    t = jnp.minimum(jnp.searchsorted(ends, idx, side="right"), S // tile - 1)
    chunk = idx - (ends - per)[t] + first[t]
    # a place past the list keeps the last item's tile: its index maps move
    # no query tile in and, above all, no output tile out
    t = jnp.where(idx < n, t, t[jnp.maximum(n - 1, 0)])
    return (t.astype(jnp.int32), jnp.where(idx < n, chunk, 0).astype(
        jnp.int32), n.astype(jnp.int32))


def _prefill_kernel(row_ref, start_ref, len_ref, tile_ref, chunk_ref, n_ref,
                    q_ref, k_hbm, v_hbm, o_ref, k_ref, v_ref, sem, acc_ref,
                    m_ref, l_ref, *, scale, bs, mb, ts, window):
    i = pl.program_id(0)
    ct = k_ref.shape[0]
    cb = ct // bs
    rows = q_ref.shape[1]                        # rep * ts: row r * ts + j

    @pl.when(i < n_ref[0])                       # places past the list: idle
    def _item():
        t, c = tile_ref[i], chunk_ref[i]
        q0 = start_ref[0] + t * ts               # tile's first abs position
        # the keys the tile's real rows may read: up to its last real row,
        # from its first row's oldest key
        hi = jnp.minimum(q0 + ts, len_ref[0]) - 1
        lo = jnp.maximum(q0 - (window - 1), 0) if window else 0

        @pl.when(c == lo // ct)                  # the tile's first chunk
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        # one copy a live block of the chunk and side, by table value, all
        # started then all awaited (the decode kernel's pattern): no block
        # past the tile's last real row, none wholly behind its first row's
        # window (what the buffers hold there is dropped below)
        def block_copies(j):
            blk = row_ref[jnp.minimum(c * cb + j, mb - 1)]
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

        live = jnp.minimum(hi // bs - c * cb + 1, cb)
        head = jnp.maximum(lo // bs - c * cb, 0)
        jax.lax.fori_loop(head, live, start, 0)
        jax.lax.fori_loop(head, live, wait, 0)

        op = k_ref.dtype                         # the MXU's operands
        # kv heads lead: a KV head's rep * ts query rows are the rows of ONE
        # matmul against the chunk's keys, and of one against its values
        k = jnp.swapaxes(k_ref[...].astype(jnp.float32), 0, 1)  # [Hkv,ct,D]
        v = jnp.swapaxes(v_ref[...].astype(jnp.float32), 0, 1)
        pos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (1, ct, 1), 1)
        # a weight of zero does not hide a NaN: values no real row of the
        # tile may read (past the prompt, or in a block not copied) go
        v = jnp.where((pos <= hi) & (pos >= lo), v, 0.0)
        s = jnp.einsum("grd,gkd->grk", q_ref[...], k.astype(op),
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32) * scale
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 0)
        j = r & (ts - 1) if ts & (ts - 1) == 0 else r % ts
        # a pad row of the tile reads what the last real row reads
        qpos = jnp.minimum(q0 + j, hi)
        kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 1)
        mask = kpos <= qpos                      # abs-position causal mask
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask[None], s, NEG_INF)    # [Hkv, rows, ct]
        m_prev = m_ref[:, :, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        # a row that has met no key of its own yet keeps nothing
        p = jnp.where(mask[None], jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, :, 0:1] * corr + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "grk,gkd->grd", p.astype(op), v.astype(op),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        @pl.when(c == hi // ct)                  # the tile's last chunk
        def _finalize():                         # l > 0: a row reads itself
            o_ref[...] = (acc_ref[...] / l_ref[:, :, 0:1]).astype(o_ref.dtype)


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
    "tile", "chunk_tokens", "window", "interpret"))
def _prefill_call(q, k_pool, v_pool, block_row, start, length, *, tile,
                  chunk_tokens, window, interpret):
    _, S, H, head_dim = q.shape
    bs, Hkv, D = k_pool.shape[1:]
    rep = H // Hkv
    mb = block_row.shape[0]
    ts, ct = tile, chunk_tokens
    tiles = S // ts
    scale = 1.0 / (head_dim ** 0.5)
    places = prefill_places(S, ts, ct, mb, bs, window)
    t, chunk, n = prefill_work_list(start, length, S=S, tile=ts,
                                    chunk_tokens=ct, window=window,
                                    places=places)
    kernel = functools.partial(_prefill_kernel, scale=scale, bs=bs, mb=mb,
                               ts=ts, window=window)
    # query head h = g * rep + r, tile t, row j  ->  q_g[g, t, r * ts + j]:
    # a tile's block is the rows of one matmul a KV head, no in-kernel repeat
    q_g = _to_lanes(q[0], D).astype(k_pool.dtype).reshape(
        tiles, ts, Hkv, rep, D).transpose(2, 0, 3, 1, 4).reshape(
        Hkv, tiles * rep * ts, D)
    qo_spec = pl.BlockSpec(
        (Hkv, rep * ts, D), lambda i, row, st, ln, tl, ch, n: (0, tl[i], 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(places,),                          # static: see the docstring
        in_specs=[qo_spec, pool_spec, pool_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((ct, Hkv, D), k_pool.dtype),
            pltpu.VMEM((ct, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, ct // bs)),
            pltpu.VMEM((Hkv, rep * ts, D), jnp.float32),
            pltpu.VMEM((Hkv, rep * ts, 128), jnp.float32),
            pltpu.VMEM((Hkv, rep * ts, 128), jnp.float32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_g.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_prefill_attention",
    )(block_row.astype(jnp.int32), start.reshape(1), length.reshape(1), t,
      chunk, n.reshape(1), q_g, k_pool, v_pool)
    out = o_g.reshape(Hkv, tiles, rep, ts, D).transpose(1, 3, 0, 2, 4).reshape(
        S, H, D)[..., :head_dim]
    # the tiles never visited, and the pad rows of the last one that was
    return jnp.where((jnp.arange(S) < length - start)[:, None, None], out,
                     0)[None]

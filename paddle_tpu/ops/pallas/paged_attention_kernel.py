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
  One query row per head is no work for the MXU, so scores and the weighted
  sum are elementwise products reduced on the VPU with ``(Hkv, D)`` kept as
  the minor dims throughout (Mosaic refuses the head-batched
  ``einsum("hd,jhd->hj")`` this replaced).  Keys past a slot's length are
  masked in the scores and *values* past it are dropped before the product
  (a weight of zero does not hide a NaN), so nothing a dead block or the
  tail of the last live one holds reaches an output.
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
``(slots, block_size, max_blocks, heads, head_dim)`` — block ids,
lengths, the active mask and the work list are *values*, so the serving
engine's zero-recompile discipline holds unchanged.  All accumulation is
f32 (matching the oracle's f32
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
#: rows of a matmul (a float32 sublane tile); fewer are multiplied a row at
#: a time on the VPU (one row is no work for the MXU)
MXU_QUERY_ROWS = 8

#: query rows per prefill grid step (f32 q/out/acc tiles of 16 heads x
#: 128 rows x 64->128 lanes are 1 MiB each; ~7 MiB of VMEM in all)
PREFILL_Q_TILE = 128


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
    """A work item's online-softmax update where a KV head serves a sublane
    tile of query heads or more (grouped queries: 8 of them at 32 / 4): the
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

def _first_block(q0, window: int, block_size: int):
    """The first block a query tile that starts at ``q0`` reads: the one that
    holds the first key of its first query's window (block 0 with none)."""
    return jnp.maximum(q0 - (window - 1), 0) // block_size if window else 0


def _prefill_kernel(row_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale, block_size, window, mb):
    t, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    Hkv, rep, ts, _ = q_ref.shape

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q0 = start_ref[0] + t * ts                   # tile's first abs position
    # the grid's places count from the first block the tile reads: block 0,
    # or with a window the block of the first query's oldest key
    a = _first_block(q0, window, block_size) + i
    # the last live key position is the tile's last query's absolute
    # position; blocks wholly past it contribute nothing (pure prefix
    # blocks below `start` are always live — the fused cross-attention half)
    live = (a * block_size <= q0 + ts - 1) & (a < mb)

    @pl.when(live)
    def _compute():
        # kv heads lead, so each contraction is a head-batched matmul
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)  # [Hkv,BS,D]
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)
        shape = (Hkv, ts, block_size)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        kpos = a * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 2)
        mask = kpos <= qpos                      # abs-position causal mask
        if window:
            mask &= kpos > qpos - window
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
                                   *, window=0, interpret=False):
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
        window:    0, or the keys a query reads: ``j <= i`` AND ``j > i -
                   window``.  A query tile's grid places then cover only the
                   blocks from its first query's oldest key to its last
                   query (``(window + tile) / block_size`` places and not the
                   whole row), so no block wholly outside every query's
                   window is visited — its table entry may have been
                   released.

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
    window = int(window)
    kernel = functools.partial(_prefill_kernel, scale=scale,
                               block_size=block_size, window=window, mb=MB)
    # blocks a tile's places cover: the whole row, or a window and a tile
    places = min(MB, (window + ts - 2) // block_size + 2) if window else MB
    # head-major queries, query head h = g * rep + r  ->  q_g[g, r]
    q_g = q[0].transpose(1, 0, 2).reshape(Hkv, rep, S, D)

    def kv_index(t, i, row, st):
        a = _first_block(st[0] + t * ts, window, block_size) + i
        return (row[jnp.minimum(a, MB - 1)], 0, 0, 0)

    kv_spec = pl.BlockSpec((1, block_size, Hkv, D), kv_index)
    qo_spec = pl.BlockSpec((Hkv, rep, ts, D),
                           lambda t, i, row, st: (0, 0, t, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S // ts, places),
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

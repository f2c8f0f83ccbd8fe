"""Pallas TPU latent (MLA) paged attention: absorbed-form decode and tail
prefill straight off a one-vector-per-token block pool.

A latent-attention model caches ONE vector a token a layer, ``[c_kv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` numbers, after the norm and the rotation),
shared by every head and key and value at once.  In the absorbed form the
per-head up-projection of the keys is folded into the query
(``q_lat = q_nope W^K``) and that of the values is applied to the output
(``o = o_lat W^V``), so attention itself is ``H`` query rows of width
``rank + rope`` against one key row a token, and the value is the first
``rank`` lanes of the same row: a ``[H, Dp] x [Dp, tokens]`` product, which
is MXU work (the per-KV-head kernels of ``paged_attention_kernel`` multiply
one query row per head on the VPU).  ``q_lat`` and the ``W^V`` product stay
outside the kernels as plain matmuls.

Both kernels read the pool ``[num_blocks, block_size, Dp]`` (``Dp`` = the
latent width in whole 128-lane rows, pad lanes zero) where it lies, by block
table *value*: the pool stays in HBM (``memory_space=ANY``) and every grid
step copies one **chunk** of ``CHUNK_TOKENS`` tokens' blocks into VMEM with
one DMA a block, then does one scores product and one value product over the
whole chunk.  A grid step a 16-token block would cost the step's fixed
overhead (~0.35 us) 512 times a slot at 8,192 positions; a 256-token chunk
makes the products wide enough for the MXU and the steps few.

- **decode** (``mla_paged_decode``): the grid is the *work list*, not
  ``slots x blocks``: one step per (active slot, live chunk), built outside
  the kernel from the lengths and handed over in scalar prefetch with its
  length as the grid's (dynamic) bound, so idle slots and the blocks past a
  slot's length cost nothing.  Output rows of slots without work are not
  written; the wrapper zeroes them.
- **prefill** (``mla_paged_prefill``): grid ``(query tiles, chunks)``; a tile
  of ``PREFILL_Q_TOKENS`` tail tokens (all heads: rows are token-major,
  head-minor, as the projections leave them, so nothing is transposed)
  attends over the slot's whole block row, cached prefix and fresh tail,
  under the absolute-position causal mask.  Chunks wholly past the tile and
  tiles wholly past the prompt's real length are skipped (their output is
  zero).  Sized for v5e's 16 MiB of scoped VMEM with bf16 operands.

Softmax statistics and accumulators are float32; operands go to the MXU in
the dtype they arrive in.  Each kernel has a jnp oracle (gather + masked
softmax) that ``kernel="reference"`` selects; parity is asserted in
tests/test_paged_kernel.py in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: tokens a grid step attends over (whole blocks; at least one)
CHUNK_TOKENS = 256
#: tail tokens per prefill query tile (x heads = rows of the tile)
PREFILL_Q_TOKENS = 32


def _chunk_blocks(block_size: int, max_blocks: int) -> int:
    return max(1, min(CHUNK_TOKENS // block_size, max_blocks))


def chunk_tokens(block_size: int, max_blocks: int) -> int:
    """Tokens one work item of the decode kernel attends over."""
    return _chunk_blocks(block_size, max_blocks) * block_size


def _fetch_chunk(tbl_row, first_block, pool_ref, kv_ref, sem, *, cb, bs, mb):
    """Copies blocks ``first_block .. first_block+cb-1`` of a table row into
    ``kv_ref [cb*bs, Dp]``.  Indices past the row are clamped: what they
    bring is past every length and masked."""
    copies = []
    for j in range(cb):                               # static
        blk = tbl_row(jnp.minimum(first_block + j, mb - 1))
        cp = pltpu.make_async_copy(pool_ref.at[blk],
                                   kv_ref.at[pl.ds(j * bs, bs)], sem.at[j])
        cp.start()
        copies.append(cp)
    for cp in copies:
        cp.wait()


def _attend(q, kv_ref, mask, acc_ref, m_ref, l_ref, *, scale, dv):
    """One online-softmax update of ``acc/m/l`` with a chunk: ``q [R, Dp]``
    against ``kv_ref [T, Dp]``; values are lanes ``0..dv-1`` of the keys."""
    k = kv_ref[...]
    # DEFAULT, whatever the process-wide matmul precision: the operands are
    # what the MXU takes in one pass, and Mosaic refuses bf16 at "highest"
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.DEFAULT,
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)                   # [R, T]
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    # a row with nothing visible yet has m_new = NEG_INF and p = 1: dropped
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv = jnp.dot(p.astype(k.dtype), kv_ref[:, 0:dv],
                 precision=jax.lax.Precision.DEFAULT,
                 preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _finish(o_ref, acc_ref, l_ref):
    l = l_ref[:, 0:1]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                  ).reshape(o_ref.shape).astype(o_ref.dtype)


# -- decode ------------------------------------------------------------------

def _decode_kernel(tbl_ref, len_ref, slot_ref, chunk_ref, q_ref, pool_ref,
                   o_ref, kv_ref, sem, acc_ref, m_ref, l_ref, *, scale, dv,
                   cb, bs, mb):
    i = pl.program_id(0)
    b, c = slot_ref[i], chunk_ref[i]
    length = len_ref[b]                         # window 0..length inclusive
    ct = cb * bs

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    _fetch_chunk(lambda j: tbl_ref[b, j], c * cb, pool_ref, kv_ref, sem,
                 cb=cb, bs=bs, mb=mb)
    q = q_ref[0]                                # [H, Dp]
    kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], ct), 1)
    _attend(q, kv_ref, kpos <= length, acc_ref, m_ref, l_ref,
            scale=scale, dv=dv)

    @pl.when(c == length // ct)                 # the slot's last live chunk
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def decode_work_list(lengths, active, chunk_tokens: int, max_chunks: int):
    """``(slot, chunk, n)``: the (slot, chunk) pairs a decode step has to
    visit, slot-major, in the first ``n`` places of two ``[B * max_chunks]``
    arrays.  A slot with ``active == 0`` has none; an active one has the
    chunks that intersect ``0..lengths[slot]``."""
    B = lengths.shape[0]
    per = jnp.where(active > 0,
                    jnp.minimum(lengths // chunk_tokens + 1, max_chunks), 0)
    ends = jnp.cumsum(per)
    n = ends[-1]
    idx = jnp.arange(B * max_chunks, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, idx, side="right"), B - 1
                       ).astype(jnp.int32)
    chunk = idx - (ends - per)[slot]
    live = idx < n
    return (jnp.where(live, slot, 0), jnp.where(live, chunk, 0).astype(
        jnp.int32), n.astype(jnp.int32))


def mla_paged_decode(q_lat, pool, block_tables, lengths, active, *, scale,
                     dv, interpret=False):
    """One decode step of absorbed latent attention off the block pool.

    Args:
        q_lat:        ``[B, H, Dp]`` absorbed queries ``[q_nope W^K | q_rope]``,
                      zero in the pad lanes.
        pool:         ``[num_blocks, block_size, Dp]`` one layer's latent pool
                      (the current token already written).
        block_tables: ``[B, max_blocks]`` int32.
        lengths:      ``[B]`` int32 current token index per slot.
        active:       ``[B]`` int32, nonzero for the running slots.
        scale:        softmax scale (``qk_head_dim ** -0.5``).
        dv:           value width: lanes ``0..dv-1`` of a pool row.

    Returns:
        ``[B, H, dv]`` latent context; zero for slots that are not active.
    """
    B, H, Dp = q_lat.shape
    bs = pool.shape[1]
    mb = block_tables.shape[1]
    cb = _chunk_blocks(bs, mb)
    max_chunks = -(-mb // cb)
    lengths = lengths.astype(jnp.int32)
    slot, chunk, n = decode_work_list(lengths, active, cb * bs, max_chunks)
    kernel = functools.partial(_decode_kernel, scale=scale, dv=dv, cb=cb,
                               bs=bs, mb=mb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, H, Dp), lambda i, t, ln, sl, ch: (sl[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, dv),
                               lambda i, t, ln, sl, ch: (sl[i], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((cb * bs, Dp), pool.dtype),
            pltpu.SemaphoreType.DMA((cb,)),
            pltpu.VMEM((H, dv), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_paged_decode",
    )(block_tables.astype(jnp.int32), lengths, slot, chunk, q_lat, pool)
    return jnp.where((active > 0)[:, None, None], out, 0)


def mla_decode_reference(q_lat, pool, block_tables, lengths, active, *,
                         scale, dv):
    """The jnp oracle of :func:`mla_paged_decode`: gather every slot's row of
    blocks contiguous, masked softmax in float32."""
    B, MB = block_tables.shape
    bs, Dp = pool.shape[1:]
    kv = jnp.take(pool, block_tables.reshape(-1), axis=0
                  ).reshape(B, MB * bs, Dp)
    s = jnp.einsum("bhd,btd->bht", q_lat, kv,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(MB * bs, dtype=jnp.int32)
    s = jnp.where(kpos[None, None, :] <= lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    out = jnp.einsum("bht,btd->bhd", p, kv[..., :dv],
                     preferred_element_type=jnp.float32).astype(q_lat.dtype)
    return jnp.where((active > 0)[:, None, None], out, 0)


# -- prefill -----------------------------------------------------------------

def _prefill_kernel(row_ref, start_ref, len_ref, q_ref, pool_ref, o_ref,
                    kv_ref, sem, acc_ref, m_ref, l_ref, *, scale, dv, cb, bs,
                    mb, heads, tq):
    t, c = pl.program_id(0), pl.program_id(1)
    nc = pl.num_programs(1)
    ct = cb * bs
    rows = tq * heads

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = start_ref[0] + t * tq                  # the tile's first position
    # live: the chunk starts at or before the tile's last query, and the
    # tile holds a real token of the prompt (pad rows past it are not read)
    live = jnp.logical_and(c * ct <= q0 + tq - 1, q0 < len_ref[0])

    @pl.when(live)
    def _compute():
        _fetch_chunk(lambda j: row_ref[j], c * cb, pool_ref, kv_ref, sem,
                     cb=cb, bs=bs, mb=mb)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 0)
        tok = (row >> (heads.bit_length() - 1)) if heads & (heads - 1) == 0 \
            else row // heads                   # rows: token-major, head-minor
        kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (rows, ct), 1)
        _attend(q_ref[...], kv_ref, kpos <= q0 + tok, acc_ref, m_ref, l_ref,
                scale=scale, dv=dv)

    @pl.when(c == nc - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def _q_tokens(S: int) -> int:
    """Largest power-of-two tile <= PREFILL_Q_TOKENS dividing S (an S that
    none divides runs as one tile)."""
    t = min(PREFILL_Q_TOKENS, S)
    while t > 1 and S % t:
        t //= 2
    return t if S % t == 0 else S


def mla_paged_prefill(q_lat, pool, block_row, start, length, *, scale, dv,
                      interpret=False):
    """Tail-bucket prefill of absorbed latent attention off the block pool.

    Args:
        q_lat:     ``[S, H, Dp]`` absorbed tail queries at absolute positions
                   ``start .. start+S-1``, zero in the pad lanes.
        pool:      ``[num_blocks, block_size, Dp]`` one layer's latent pool
                   (the tail already written).
        block_row: ``[max_blocks]`` int32, the slot's row of the table.
        start:     int32 scalar: the cached prefix's length (a block boundary).
        length:    int32 scalar: the prompt's real length; tiles wholly at or
                   past it are not computed.

    Returns:
        ``[S, H, dv]`` latent context (zero in skipped tiles).
    """
    S, H, Dp = q_lat.shape
    bs = pool.shape[1]
    mb = block_row.shape[0]
    cb = _chunk_blocks(bs, mb)
    tq = _q_tokens(S)
    kernel = functools.partial(_prefill_kernel, scale=scale, dv=dv, cb=cb,
                               bs=bs, mb=mb, heads=H, tq=tq)
    rows = tq * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // tq, -(-mb // cb)),
        in_specs=[
            pl.BlockSpec((rows, Dp), lambda t, c, r, st, ln: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((rows, dv), lambda t, c, r, st, ln: (t, 0)),
        scratch_shapes=[
            pltpu.VMEM((cb * bs, Dp), pool.dtype),
            pltpu.SemaphoreType.DMA((cb,)),
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * H, dv), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_paged_prefill",
    )(block_row.astype(jnp.int32),
      jnp.asarray(start, jnp.int32).reshape(1),
      jnp.asarray(length, jnp.int32).reshape(1),
      q_lat.reshape(S * H, Dp), pool)
    return out.reshape(S, H, dv)


def mla_prefill_reference(q_lat, pool, block_row, start, length, *, scale,
                          dv):
    """The jnp oracle of :func:`mla_paged_prefill` (computes the pad rows
    too; what is compared is the rows below ``length``)."""
    del length
    S = q_lat.shape[0]
    bs, Dp = pool.shape[1:]
    kv = jnp.take(pool, block_row, axis=0).reshape(-1, Dp)
    s = jnp.einsum("shd,td->sht", q_lat, kv,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.asarray(start, jnp.int32).reshape(()) + jnp.arange(
        S, dtype=jnp.int32)
    kpos = jnp.arange(kv.shape[0], dtype=jnp.int32)
    s = jnp.where(kpos[None, None, :] <= qpos[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("sht,td->shd", p, kv[:, :dv],
                      preferred_element_type=jnp.float32).astype(q_lat.dtype)

"""Pallas TPU latent (MLA) paged attention: absorbed decode, and a tail
prefill that takes each (query, key) pair in the form its key's place asks
for.

A latent-attention model caches ONE vector a token a layer, ``[c_kv | k_rope]``
(``kv_lora_rank + qk_rope_head_dim`` numbers, after the norm and the rotation),
shared by every head and key and value at once.  In the **absorbed** form the
per-head up-projection of the keys is folded into the query
(``q_lat = q_nope W^K``) and that of the values is applied to the output
(``o = o_lat W^V``), so attention itself is ``H`` query rows of width
``rank + rope`` against one key row a token, and the value is the first
``rank`` lanes of the same row: a ``[H, Dp] x [Dp, tokens]`` product, which
is MXU work (the per-KV-head kernels of ``paged_attention_kernel`` multiply
one query row per head on the VPU).  ``q_lat`` and the ``W^V`` product stay
outside the kernels as plain matmuls.  That is right where a key is met by
few queries and is read from the pool as it is stored — a decode step, and a
question behind a cached prefix — and wrong for a prompt's own tail, where
the same key is met by thousands of queries: up-projecting it once
(``c_kv W^K``, ``c_kv W^V``: 2 x rank x (nope + v) operations a head) makes
every pair cost 2 x (nope + rope) + 2 x v operations instead of 2 x (rank +
rope) + 2 x rank (640 against 2,176 at the published widths), paid back
after ~170 queries.

The absorbed kernels read the pool ``[num_blocks, block_size, Dp]`` (``Dp`` =
the latent width in whole 128-lane rows, pad lanes zero) where it lies, by
block table *value*: the pool stays in HBM (``memory_space=ANY``) and every
grid step copies one **chunk** of ``CHUNK_TOKENS`` tokens' blocks into VMEM
with one DMA a block, then does one scores product and one value product over
the whole chunk.  A grid step a 16-token block would cost the step's fixed
overhead (~0.35 us) 512 times a slot at 8,192 positions; a 256-token chunk
makes the products wide enough for the MXU and the steps few.

- **decode** (``mla_paged_decode``): the grid is the *work list*, not
  ``slots x blocks``: one step per (active slot, live chunk), built outside
  the kernel from the lengths and handed over in scalar prefetch with its
  length as the grid's (dynamic) bound, so idle slots and the blocks past a
  slot's length cost nothing.  Output rows of slots without work are not
  written; the wrapper zeroes them.
- **prefill** (``mla_prefill``), a tail bucket of ``S`` rows at ``start``:
  - *the square, tail queries over tail keys* (``mla_flash_prefill``): the
    tail's latents are in the program's hands (they were just written to the
    pool), so nothing is read from it: they are up-projected once and the
    tail attends to itself in one causal flash forward, a head a grid row,
    ``FLASH_BLOCK`` query rows against ``FLASH_BLOCK`` key rows a grid step.
    The score tile is held ``[keys, queries]`` (statistics are lane-dense
    rows, reductions run down the sublanes: ``flash_attention_kernel``'s
    lesson) and the operands arrive in the layouts that need no transpose
    inside the kernel (``q`` and ``v`` with the tail along the lanes).  The
    walk is unrolled at trace time by pieces of ``FLASH_SUB`` queries: a
    piece meets the whole key block under the diagonal and the keys up to
    its own last row on it (only the square the diagonal crosses builds a
    mask), and a piece wholly of pad rows past the prompt's real length is
    not computed.
  - *the rectangle, tail queries over the ``start`` cached tokens*
    (``mla_paged_prefill``): absorbed, off the pool, no mask but ``key <
    start``; grid ``(query tiles, chunks)``, a tile of ``PREFILL_Q_TOKENS``
    tail tokens (all heads: rows are token-major, head-minor, as the
    projections leave them).  Chunks at or past ``start`` and tiles wholly
    past the prompt's real length are skipped; a cold prompt's program run
    skips the kernel (``lax.cond`` on ``start > 0``).
  - both return their float32 log-sum-exp beside the normalised output, and
    the two parts are merged by it (``merge_partials``).
  Sized for v5e's 16 MiB of scoped VMEM with bf16 operands.

Softmax statistics and accumulators are float32; operands go to the MXU in
the dtype they arrive in.  ``kernel="reference"`` selects the jnp oracles
(gather + one masked absorbed softmax); parity is asserted in
tests/test_paged_kernel.py in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import _precision_for

NEG_INF = -1e30

#: named scopes of the two changes of form in a compiled program's op names:
#: ``q_nope W^K`` and ``o_lat W^V`` around an absorbed call, ``c_kv W^K`` and
#: ``c_kv W^V`` before the up-projected one
ABSORB_SCOPE = "mla.absorb"
UPPROJECT_SCOPE = "mla.upproject"

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


def _reset(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


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
        _reset(acc_ref, m_ref, l_ref)

    _fetch_chunk(lambda j: tbl_ref[b, j], c * cb, pool_ref, kv_ref, sem,
                 cb=cb, bs=bs, mb=mb)
    q = q_ref[0]                                # [H, Dp]
    kpos = c * ct + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], ct), 1)
    _attend(q, kv_ref, kpos <= length, acc_ref, m_ref, l_ref,
            scale=scale, dv=dv)

    @pl.when(c == length // ct)                 # the slot's last live chunk
    def _done():
        _finish(o_ref, acc_ref, l_ref)


def decode_work_list(lengths, active, chunk_tokens: int, max_chunks: int,
                     first=None):
    """``(slot, chunk, n)``: the (slot, chunk) pairs a decode step has to
    visit, slot-major, in the first ``n`` places of two ``[B * max_chunks]``
    arrays.  A slot with ``active == 0`` has none; an active one has the
    chunks that intersect ``0..lengths[slot]`` — from chunk ``first[slot]``
    on where a caller gives one (a layer that reads only the keys of a window
    behind the query)."""
    B = lengths.shape[0]
    last = jnp.minimum(lengths // chunk_tokens + 1, max_chunks)
    if first is None:
        first = jnp.zeros_like(last)
    per = jnp.where(active > 0, last - first, 0)
    ends = jnp.cumsum(per)
    n = ends[-1]
    idx = jnp.arange(B * max_chunks, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, idx, side="right"), B - 1
                       ).astype(jnp.int32)
    chunk = idx - (ends - per)[slot] + first[slot]
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


# -- prefill: the cached prefix, absorbed -------------------------------------

def _prefix_kernel(row_ref, start_ref, len_ref, q_ref, pool_ref, o_ref,
                   lse_ref, kv_ref, sem, acc_ref, m_ref, l_ref, *, scale, dv,
                   cb, bs, mb, tq):
    t, c = pl.program_id(0), pl.program_id(1)
    nc = pl.num_programs(1)
    ct = cb * bs
    start = start_ref[0]

    @pl.when(c == 0)
    def _init():
        _reset(acc_ref, m_ref, l_ref)

    # live: the chunk holds a cached token, and the tile a real token of the
    # prompt (pad rows past it are not read)
    live = jnp.logical_and(c * ct < start, start + t * tq < len_ref[0])

    @pl.when(live)
    def _compute():
        _fetch_chunk(lambda j: row_ref[j], c * cb, pool_ref, kv_ref, sem,
                     cb=cb, bs=bs, mb=mb)
        kpos = c * ct + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[0], ct), 1)
        _attend(q_ref[...], kv_ref, kpos < start, acc_ref, m_ref, l_ref,
                scale=scale, dv=dv)

    @pl.when(c == nc - 1)
    def _done():
        _finish(o_ref, acc_ref, l_ref)
        l = l_ref[...]
        lse = m_ref[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        lse_ref[0] = lse.T[0:1, :]              # the column as a lane-dense row


def _dividing(S: int, preferred: int) -> int:
    """Largest power-of-two size <= ``preferred`` dividing S (an S that none
    divides is one piece)."""
    t = min(preferred, S)
    while t > 1 and S % t:
        t //= 2
    return t if S % t == 0 else S


def mla_paged_prefill(q_lat, pool, block_row, start, length, *, scale, dv,
                      interpret=False):
    """A tail bucket's absorbed attention over the slot's **cached prefix**
    alone, off the block pool: the keys at positions ``0 .. start-1``, which
    every tail query sees (no causal mask), as one part of a two-part
    softmax — the tail's own keys are :func:`mla_flash_prefill`'s.

    Args:
        q_lat:     ``[S, H, Dp]`` absorbed tail queries, zero in the pad
                   lanes.
        pool:      ``[num_blocks, block_size, Dp]`` one layer's latent pool.
        block_row: ``[max_blocks]`` int32, the slot's row of the table.
        start:     int32 scalar: the cached prefix's length (a block
                   boundary); chunks wholly at or past it are not read.
        length:    int32 scalar: the prompt's real length; tiles wholly at or
                   past it are not computed.

    Returns:
        ``(o [S, H, dv], lse [S, H])``: the part's normalised latent context
        and its float32 log-sum-exp (zero and ``NEG_INF`` in skipped tiles
        and where ``start`` is 0).
    """
    S, H, Dp = q_lat.shape
    bs = pool.shape[1]
    mb = block_row.shape[0]
    cb = _chunk_blocks(bs, mb)
    tq = _dividing(S, PREFILL_Q_TOKENS)
    kernel = functools.partial(_prefix_kernel, scale=scale, dv=dv, cb=cb,
                               bs=bs, mb=mb, tq=tq)
    rows = tq * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // tq, -(-mb // cb)),
        in_specs=[
            pl.BlockSpec((rows, Dp), lambda t, c, r, st, ln: (t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((rows, dv), lambda t, c, r, st, ln: (t, 0)),
            pl.BlockSpec((1, 1, rows), lambda t, c, r, st, ln: (t, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((cb * bs, Dp), pool.dtype),
            pltpu.SemaphoreType.DMA((cb,)),
            pltpu.VMEM((rows, dv), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S * H, dv), q_lat.dtype),
                   jax.ShapeDtypeStruct((S // tq, 1, rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_paged_prefill",
    )(block_row.astype(jnp.int32),
      jnp.asarray(start, jnp.int32).reshape(1),
      jnp.asarray(length, jnp.int32).reshape(1),
      q_lat.reshape(S * H, Dp), pool)
    return out.reshape(S, H, dv), lse.reshape(S, H)


# -- prefill: the tail over itself, up-projected -------------------------------

#: tail rows a grid step of the flash pass owns (queries) and is handed
#: (keys), and the queries a piece of its walk takes
FLASH_BLOCK = 1024
FLASH_SUB = 256


def _mm(a, b):
    return jnp.dot(a, b, precision=_precision_for(a.dtype),
                   preferred_element_type=jnp.float32)


def _flash_kernel(real_ref, q_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *, scale, nope, bq, sub, n_blocks):
    i, im = pl.program_id(1), pl.program_id(2)
    real = real_ref[0]

    @pl.when(im == 0)
    def _init():
        _reset(acc_ref, m_ref, l_ref)

    def piece(jq, keys, diagonal):
        """Queries ``jq*sub ..`` of the block against the first ``keys`` rows
        of the key block, as one ``[keys, sub]`` tile: the queries lie along
        the lanes, so the statistics are lane-dense rows and the reductions
        run down the sublanes (PERF.md §6, PR 31)."""
        qs = slice(jq * sub, (jq + 1) * sub)
        s = (_mm(kn_ref[0, :keys, :], q_ref[0, :nope, qs])
             + _mm(kr_ref[:keys, :], q_ref[0, nope:, qs])) * scale
        if diagonal:
            # only the square the diagonal crosses builds a mask: its keys
            # and its queries start at the same row of the tail
            sq = s[keys - sub:, :]
            key = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 0)
            query = jax.lax.broadcasted_iota(jnp.int32, sq.shape, 1)
            sq = jnp.where(query >= key, sq, NEG_INF)
            s = sq if keys == sub else jnp.concatenate(
                [s[:keys - sub, :], sq], axis=0)
        m_prev = m_ref[:, qs]                                   # [1, sub]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:, qs] = l_ref[:, qs] * corr + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[:, qs] = acc_ref[:, qs] * corr + _mm(
            v_ref[0, :, :keys], p.astype(v_ref.dtype))          # [Dv, sub]
        m_ref[:, qs] = m_new

    # the walk is unrolled at trace time; a piece wholly of pad rows (past
    # the tail's real length) is not computed, one above the diagonal does
    # not exist: a piece on it meets the keys up to its own last row
    for jq in range(bq // sub):
        live = i * bq + jq * sub < real
        if n_blocks > 1:
            pl.when(jnp.logical_and(live, im < i))(
                functools.partial(piece, jq, bq, False))
        pl.when(jnp.logical_and(live, im == i))(
            functools.partial(piece, jq, (jq + 1) * sub, True))

    @pl.when(im == n_blocks - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def flash_sizes(S: int, block=None, sub=None):
    """``(block, sub)`` of a tail bucket of ``S`` rows."""
    block = _dividing(S, FLASH_BLOCK) if block is None else block
    sub = _dividing(block, FLASH_SUB) if sub is None else sub
    if S % block or block % sub:
        raise ValueError(f"a tail of {S} rows must divide into blocks of "
                         f"{block}, a block into pieces of {sub}")
    return block, sub


def mla_flash_prefill(q_t, k_nope, k_rope, v_t, real, *, scale, block=None,
                      sub=None, interpret=False):
    """Causal attention of a tail bucket over itself in the up-projected
    form: one flash forward with ``nope + rope``-wide queries and keys and
    ``Dv``-wide values, a head a grid row.  Operands come in the layouts the
    up-projections can leave them in, so that no product inside the kernel
    transposes anything: the score tile is ``[keys, queries]`` = ``k @ q_t``
    and the context ``[Dv, queries]`` = ``v_t @ p``.

    Args:
        q_t:    ``[H, nope + rope, S]`` queries ``[q_nope | q_rope]``, the
                tail along the lanes.
        k_nope: ``[H, S, nope]`` up-projected keys ``c_kv W^K``.
        k_rope: ``[S, rope]`` the rotary key, one for all heads.
        v_t:    ``[H, Dv, S]`` up-projected values ``c_kv W^V``.
        real:   int32 scalar: the tail's real rows; pieces of the walk
                wholly at or past it are not computed.
        block, sub: pin a size of :func:`flash_sizes` (tests, timing).

    Returns:
        ``(o_t [H, Dv, S], lse [H, S])``: the normalised context in the
        queries' dtype and its float32 log-sum-exp (zero and ``NEG_INF`` in
        skipped pieces).
    """
    H, D, S = q_t.shape
    nope, Dv = k_nope.shape[2], v_t.shape[1]
    bq, sub = flash_sizes(S, block, sub)
    n_blocks = S // bq

    def key_block(i, im, real):
        # a pair above the diagonal, and every pair of a block of pad rows,
        # asks for a block already in VMEM: a dead step copies nothing
        return jnp.where(i * bq < real[0], jnp.minimum(im, i), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, n_blocks, n_blocks),
        in_specs=[
            pl.BlockSpec((1, D, bq), lambda h, i, im, r: (h, 0, i)),
            pl.BlockSpec((1, bq, nope),
                         lambda h, i, im, r: (h, key_block(i, im, r), 0)),
            pl.BlockSpec((bq, D - nope),
                         lambda h, i, im, r: (key_block(i, im, r), 0)),
            pl.BlockSpec((1, Dv, bq),
                         lambda h, i, im, r: (h, 0, key_block(i, im, r))),
        ],
        out_specs=[
            pl.BlockSpec((1, Dv, bq), lambda h, i, im, r: (h, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda h, i, im, r: (h, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Dv, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
            pltpu.VMEM((1, bq), jnp.float32),
        ],
    )
    o_t, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, nope=nope, bq=bq,
                          sub=sub, n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((H, Dv, S), q_t.dtype),
                   jax.ShapeDtypeStruct((H, 1, S), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mla_flash_prefill",
    )(jnp.asarray(real, jnp.int32).reshape(1), q_t, k_nope, k_rope, v_t)
    return o_t, lse[:, 0, :]


# -- prefill: both parts ------------------------------------------------------

def merge_partials(o1, lse1, o2, lse2):
    """The softmax over two disjoint key sets from each set's normalised
    result ``o [..., D]`` and log-sum-exp ``lse [...]``; float32."""
    m = jnp.maximum(lse1, lse2)
    w1, w2 = jnp.exp(lse1 - m)[..., None], jnp.exp(lse2 - m)[..., None]
    return (w1 * o1.astype(jnp.float32) + w2 * o2.astype(jnp.float32)
            ) / (w1 + w2)


def absorb_queries(q_nope, q_rope, w_uk, lanes=None):
    """``q_lat [..., H, rank + rope] = [q_nope W^K | q_rope]``, zero-padded
    to ``lanes`` (a pool's) where given."""
    q_lat = jnp.concatenate(
        [jnp.einsum("...hn,hnr->...hr", q_nope, w_uk), q_rope], axis=-1)
    if lanes is None:
        return q_lat
    return jnp.pad(q_lat, [(0, 0)] * (q_lat.ndim - 1)
                   + [(0, lanes - q_lat.shape[-1])])


# jitted so that the unrolled walk and the two kernels are traced once a
# bucket and not once a layer and pass of the program that calls them
# (PR 27's lesson: 24 layers x the build's passes)
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_prefill(q, lat, w_uk, w_uv, pool, block_row, start, length, *,
                scale, interpret=False):
    """Tail-bucket prefill of latent attention, each (query, key) pair in
    the form its key's place asks for: the tail's own latents ``lat`` are in
    the program's hands, so they are up-projected once (``K = c_kv W^K``,
    ``V = c_kv W^V``) and the tail attends to itself in one causal flash
    pass (:func:`mla_flash_prefill`: 2 x (nope + rope) + 2 x Dv operations a
    pair and head, where the absorbed form needs 2 x (rank + rope) + 2 x
    rank); the ``start`` cached tokens lie in the pool as latents, so they
    are attended to absorbed (:func:`mla_paged_prefill`) and go through
    ``W^V`` afterwards; the two parts are merged by their softmax
    statistics.

    Args:
        q:         ``[S, H, nope + rope]`` tail queries ``[q_nope | q_rope]``
                   at absolute positions ``start .. start+S-1``.
        lat:       ``[S, rank + rope]`` the tail's latents ``[c_kv |
                   k_rope]`` (what was written to the pool).
        w_uk:      ``[H, nope, rank]``; w_uv: ``[H, rank, Dv]``.
        pool:      ``[num_blocks, block_size, Dp]`` one layer's latent pool.
        block_row: ``[max_blocks]`` int32, the slot's row of the table.
        start:     int32 scalar: the cached prefix's length.
        length:    int32 scalar: the prompt's real length.

    Returns:
        ``[S, H, Dv]`` the attention's output in the values' space (rows
        past ``length`` are pad: zero or garbage, never read).
    """
    nope, rank = w_uk.shape[1:]
    start = jnp.asarray(start, jnp.int32).reshape(())
    length = jnp.asarray(length, jnp.int32).reshape(())
    c_kv = lat[:, :rank]
    with jax.named_scope(UPPROJECT_SCOPE):
        # once a layer, rounded to the operands' dtype once
        k_nope = jnp.einsum("sr,hnr->hsn", c_kv, w_uk)
        v_t = jnp.einsum("sr,hrv->hvs", c_kv, w_uv)
    o_t, lse = mla_flash_prefill(
        q.transpose(1, 2, 0), k_nope, lat[:, rank:], v_t, length - start,
        scale=scale, interpret=interpret)
    tail = o_t.transpose(2, 0, 1)                             # [S, H, Dv]

    def behind_a_prefix():
        with jax.named_scope(ABSORB_SCOPE):
            q_lat = absorb_queries(q[..., :nope], q[..., nope:], w_uk,
                                   pool.shape[-1])
        o_lat, lse_p = mla_paged_prefill(
            q_lat, pool, block_row, start, length, scale=scale, dv=rank,
            interpret=interpret)
        with jax.named_scope(ABSORB_SCOPE):
            prefix = jnp.einsum("shr,hrv->shv", o_lat, w_uv)
        return merge_partials(tail, lse.T, prefix, lse_p).astype(q.dtype)

    # a cold prompt has no key in the pool: its program run skips the
    # absorbed kernel and the merge
    return jax.lax.cond(start > 0, behind_a_prefix, lambda: tail)


def mla_prefill_reference(q_lat, pool, block_row, start, length, *, scale,
                          dv):
    """The jnp oracle of the whole prefill in the absorbed form: tail
    queries ``q_lat [S, H, Dp]`` over the slot's whole block row (cached
    prefix + the tail just written) under the absolute-position causal mask,
    one softmax (computes the pad rows too; what is compared is the rows
    below ``length``)."""
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


def mla_prefill_oracle(q, w_uk, w_uv, pool, block_row, start, length, *,
                       scale):
    """:func:`mla_prefill`'s result by :func:`mla_prefill_reference`: every
    pair absorbed, one softmax, then ``W^V``."""
    nope, rank = w_uk.shape[1:]
    o_lat = mla_prefill_reference(
        absorb_queries(q[..., :nope], q[..., nope:], w_uk, pool.shape[-1]),
        pool, block_row, start, length, scale=scale, dv=rank)
    return jnp.einsum("shr,hrv->shv", o_lat, w_uv)

"""Pallas TPU attention over a cache of two granularities: the exact K and V
of the query's own **window** by one block table, and one learned summary key
and value a **chunk** of every window passed by another, under ONE softmax.

The mathematics (EVA attention as ``models/evabyte.py`` states it): position
``i`` lies in window ``i // W``; a query attends to the exact keys ``j <= i``
of its own window with logits ``s <q, k_j>`` and, for every chunk of every
*earlier* window, to that chunk's summary ``(k~, v~)`` with logits ``s <q,
k~>`` and no other term; the two kinds of row share the softmax's maximum and
sum.  A chunk of the query's own window is never read as a summary.

- :func:`chunk_summaries` is the summary of each chunk of a run of keys and
  values (a softmax over the chunk of ``s <k, phi>`` pools both; the pooled
  key carries ``+ mu``): plain XLA, float32, under the scope
  ``eva.summarise``; a window is 128 chunks of 16 rows, and one window a
  layer is summarised a few times a request.
- **decode** (``eva_paged_decode``): the K/V decode kernel's design
  (``paged_attention_kernel.py``) with two kinds of work item.  The grid is
  the static ``slots x max_items`` list of live (slot, item) pairs of
  ``mla_attention_kernel.decode_work_list``; a slot at position ``n`` in
  window ``w = n // W`` has ``w`` **summary items** — the window's summary
  block, ``W / chunk`` rows, ONE copy a side by the summary table's value —
  then the **exact items** of its own window, ``decode_chunk_tokens`` tokens
  each, one copy a block and side by the exact table's value.  Both land in
  the same VMEM buffers and go through the same online-softmax update (one
  query row a head: products reduced on the VPU); they differ in the copies
  and in which rows count.
- **prefill** (``eva_paged_prefill``): a tail of ``S`` queries behind a cached
  part, ``PREFILL_Q_TILE`` rows a tile on the first grid axis; a tile's items
  on the second: the summary blocks of every window before the tile's last
  row's, then ``PREFILL_KV_ROWS``-token chunks of exact keys from the start
  of the window of the tile's *first* row up to its last row.  A tile may
  straddle a window boundary (a tail starts at any block): a row takes the
  exact keys of its own window at or before it, and the summaries of the
  windows before its own — the window the tail closes is published before the
  attention call of the same layer, so its rows are there.  Per KV head a
  ``[tile, D] x [D, rows]`` matmul; operands in the pool's dtype with
  ``precision=DEFAULT`` (bf16 on the chip, one MXU pass), statistics float32.

Both kernels run under ``interpret=True`` off the chip; ``*_reference`` are
their jnp oracles (``kernel="reference"``): gather both tables, one masked
softmax.  Shapes depend on ``(slots, block_size, max_blocks, max_windows,
heads, head_dim, window, chunk)`` alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mla_attention_kernel import decode_work_list
from .paged_attention_kernel import decode_chunk_tokens

NEG_INF = -1e30
F32 = jnp.float32

#: named scopes of the two halves in a compiled program's op names
SUMMARISE_SCOPE = "eva.summarise"
ATTEND_SCOPE = "eva.attend"

#: query rows a prefill grid step owns, and exact-key rows an item copies
PREFILL_Q_TILE = 128
PREFILL_KV_ROWS = 128
#: the prefill kernel's scoped VMEM: a tile's queries, output, accumulators
#: and an item's keys, values, scores and weights for 32 heads of 128 are
#: ~27 MiB, over the compiler's default 16 of a v5e's 128
PREFILL_VMEM_BYTES = 64 << 20


def chunk_summaries(k, v, phi, mu, *, chunk: int, scale: float):
    """``(k~, v~) [..., T / chunk, H, D]`` float32 of ``k``/``v [..., T, H,
    D]``: per chunk and head ``a = softmax_j(scale <k_j, phi>)``, ``k~ = sum a
    k + mu``, ``v~ = sum a v``; ``phi``/``mu [H, D]``."""
    *lead, T, H, D = k.shape
    shape = (*lead, T // chunk, chunk, H, D)
    with jax.named_scope(SUMMARISE_SCOPE):
        k32, v32 = k.astype(F32).reshape(shape), v.astype(F32).reshape(shape)
        z = jnp.sum(k32 * phi.astype(F32), axis=-1, keepdims=True) * scale
        a = jax.nn.softmax(z, axis=-3)                  # over the chunk
        return (jnp.sum(a * k32, axis=-3) + mu.astype(F32),
                jnp.sum(a * v32, axis=-3))


def window_rows(pool, row, window_idx, *, window: int):
    """The exact rows ``[window, H, Dp]`` of window ``window_idx`` (traced
    scalar) of one layer's pool through a slot's block row ``[max_blocks]``
    (entries past the row's end are clamped: what they give is not kept)."""
    bs = pool.shape[1]
    nb = window // bs
    idx = jnp.clip(window_idx * nb + jnp.arange(nb, dtype=jnp.int32), 0,
                   row.shape[0] - 1)
    blocks = jnp.take(pool, jnp.take(row, idx), axis=0)     # [nb, bs, H, Dp]
    return blocks.reshape(window, *pool.shape[2:])


# -- the jnp oracles ---------------------------------------------------------

def _softmax_rows(s_ex, ok_ex, v_ex, s_su, ok_su, v_su, eq_e, eq_s):
    """One softmax over exact and summary rows: ``s_* [..., H, rows]``."""
    s = jnp.concatenate([jnp.where(ok_su, s_su, NEG_INF),
                         jnp.where(ok_ex, s_ex, NEG_INF)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    n = s_su.shape[-1]
    return (jnp.einsum(eq_s, p[..., :n], v_su.astype(F32))
            + jnp.einsum(eq_e, p[..., n:], v_ex.astype(F32)))


def _gather(pool, tables, q):
    """``tables [..., n]`` blocks of ``pool [N, rows, Hkv, Dp]`` as ``[..., n
    * rows, H, D]`` for queries ``q [..., H, D]`` (KV heads repeated)."""
    g = jnp.take(pool, tables, axis=0)
    g = g.reshape(*tables.shape[:-1], -1, *pool.shape[2:])[..., :q.shape[-1]]
    return jnp.repeat(g, q.shape[-2] // g.shape[-2], axis=-2)


def eva_decode_reference(q, k_pool, v_pool, ks_pool, vs_pool, tables, stables,
                         lengths, active, *, window: int, scale: float):
    """Oracle of :func:`eva_paged_decode`: ``q [B, H, D]`` -> ``[B, H, D]``
    (rows of slots that are not active are zero)."""
    B, H, D = q.shape
    k, v = _gather(k_pool, tables, q), _gather(v_pool, tables, q)
    ks, vs = _gather(ks_pool, stables, q), _gather(vs_pool, stables, q)
    rows = ks_pool.shape[1]
    q32 = q.astype(F32)
    w = lengths // window                                       # [B]
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)[None]
    ok_ex = (pos // window == w[:, None]) & (pos <= lengths[:, None])
    ok_su = (jnp.arange(ks.shape[1], dtype=jnp.int32)[None] // rows
             < w[:, None])
    s_ex = jnp.einsum("bhd,bkhd->bhk", q32, k.astype(F32)) * scale
    s_su = jnp.einsum("bhd,bkhd->bhk", q32, ks.astype(F32)) * scale
    o = _softmax_rows(s_ex, ok_ex[:, None], v, s_su, ok_su[:, None], vs,
                      "bhk,bkhd->bhd", "bhk,bkhd->bhd")
    return jnp.where((active > 0)[:, None, None], o, 0.0).astype(q.dtype)


def eva_prefill_reference(q, k_pool, v_pool, ks_pool, vs_pool, row, srow,
                          start, *, window: int, scale: float):
    """Oracle of :func:`eva_paged_prefill`: ``q [S, H, D]`` at positions
    ``start ..`` -> ``[S, H, D]``."""
    S, H, D = q.shape
    k, v = _gather(k_pool, row, q), _gather(v_pool, row, q)
    ks, vs = _gather(ks_pool, srow, q), _gather(vs_pool, srow, q)
    rows = ks_pool.shape[1]
    q32 = q.astype(F32)
    qpos = start + jnp.arange(S, dtype=jnp.int32)[:, None]
    pos = jnp.arange(k.shape[0], dtype=jnp.int32)[None]
    ok_ex = (pos // window == qpos // window) & (pos <= qpos)
    ok_su = (jnp.arange(ks.shape[0], dtype=jnp.int32)[None] // rows
             < qpos // window)
    s_ex = jnp.einsum("qhd,khd->qhk", q32, k.astype(F32)) * scale
    s_su = jnp.einsum("qhd,khd->qhk", q32, ks.astype(F32)) * scale
    o = _softmax_rows(s_ex, ok_ex[:, None], v, s_su, ok_su[:, None], vs,
                      "qhk,khd->qhd", "qhk,khd->qhd")
    return o.astype(q.dtype)


# -- the two kinds of copy both kernels make ----------------------------------

def _copy_summary_block(blk, ks_hbm, vs_hbm, k_ref, v_ref, sem, srows):
    """A window's summary block into the buffers' first ``srows`` rows: one
    copy a side."""
    cps = [pltpu.make_async_copy(pool.at[blk], buf.at[pl.ds(0, srows)],
                                 sem.at[side, 0])
           for side, (pool, buf) in enumerate(((ks_hbm, k_ref),
                                               (vs_hbm, v_ref)))]
    for cp in cps:
        cp.start()
    for cp in cps:
        cp.wait()


def _copy_exact_blocks(block_id, live, k_hbm, v_hbm, k_ref, v_ref, sem, bs):
    """The chunk's first ``live`` blocks (``block_id(j)``: the table's value)
    into the buffers, one copy a block and side: all started, then all
    awaited; the loops are the device's, so a chunk of many blocks traces as
    one.  The rest of the buffers keeps what it held."""
    def block_copies(j):
        row = pl.ds(pl.multiple_of(j * bs, bs), bs)
        return [pltpu.make_async_copy(pool.at[block_id(j)], buf.at[row],
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

    jax.lax.fori_loop(0, live, start, 0)
    jax.lax.fori_loop(0, live, wait, 0)


def exact_chunk_tokens(pool_shape, itemsize: int, window: int) -> int:
    """Exact tokens a decode work item covers: the K/V decode kernel's chunk
    for this pool's shape, at most a window."""
    _, bs, kv_heads, lanes = pool_shape
    return decode_chunk_tokens(bs, window // bs, kv_heads, lanes, itemsize)


# -- decode: one query token a slot, two kinds of work item -------------------

def decode_items(lengths, *, window: int, chunk_tokens: int):
    """Work items a layer a slot at position ``lengths`` costs a decode step:
    a summary block a window passed, then the exact chunks of its own."""
    w = lengths // window
    return w + (lengths - w * window) // chunk_tokens + 1


def _decode_kernel(tbl_ref, stbl_ref, len_ref, slot_ref, item_ref, n_ref,
                   q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_ref, v_ref,
                   sem, acc_ref, m_ref, l_ref, *, scale, cb, bs, mb, window,
                   srows):
    i = pl.program_id(0)
    rep = q_ref.shape[1]
    ct = cb * bs

    @pl.when(i < n_ref[0])                       # places past the list: idle
    def _item():
        b, c = slot_ref[i], item_ref[i]
        length = len_ref[b]
        w = length // window                     # windows passed = summaries
        e = c - w                                # exact chunk of the window
        in_window = length - w * window          # the query's place in it

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when(c < w)
        def _summary_block():
            _copy_summary_block(stbl_ref[b, c], ks_hbm, vs_hbm, k_ref, v_ref,
                                sem, srows)

        @pl.when(c >= w)
        def _exact_blocks():                     # the live blocks of chunk e
            first = w * (window // bs) + e * cb
            _copy_exact_blocks(
                lambda j: tbl_ref[b, jnp.minimum(first + j, mb - 1)],
                jnp.minimum(in_window // bs - e * cb + 1, cb), k_hbm, v_hbm,
                k_ref, v_ref, sem, bs)

        k = k_ref[...].astype(F32)               # [rows, Hkv, D]
        r_i = jax.lax.broadcasted_iota(jnp.int32, k.shape[:2] + (1,), 0)
        # the rows of the buffers that count: a summary block's, or the
        # chunk's positions up to the query's own
        valid = r_i < jnp.where(c < w, srows,
                                jnp.minimum(ct, in_window - e * ct + 1))
        # a weight of zero does not hide a NaN: rows that do not count are
        # dropped from the values too
        v = jnp.where(valid, v_ref[...].astype(F32), 0.0)
        for r in range(rep):                     # static: H // Hkv
            q = q_ref[0, r].astype(F32)          # [Hkv, D]
            s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
            s = jnp.where(valid, s, NEG_INF)     # [rows, Hkv, 1]
            m_prev = m_ref[r, :, 0:1]            # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[r, :, 0:1] * corr + jnp.sum(p, axis=0)
            acc_ref[r] = acc_ref[r] * corr + jnp.sum(p * v, axis=0)
            m_ref[r] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[r] = jnp.broadcast_to(l_new, l_ref.shape[1:])

        @pl.when(c == w + in_window // ct)       # the slot's last item
        def _finalize():
            for r in range(rep):                 # l > 0: the query's own row
                o_ref[0, r] = (acc_ref[r] / l_ref[r, :, 0:1]
                               ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def eva_paged_decode(q, k_pool, v_pool, ks_pool, vs_pool, tables, stables,
                     lengths, active, *, window: int, scale: float,
                     interpret=False):
    """One decode step off both groups of the pool.

    Args:
        q:        ``[B, H, Dp]`` current-token queries (pad lanes zero).
        k_pool:   ``[num_blocks, block_size, Hkv, Dp]`` a layer's exact keys
                  (the current token already written); ``v_pool`` alike.
        ks_pool:  ``[num_summary_blocks, rows, Hkv, Dp]`` a layer's summary
                  keys, a window a block; ``vs_pool`` alike.
        tables:   ``[B, max_blocks]`` int32 exact block ids by position.
        stables:  ``[B, max_windows]`` int32 summary block ids by window.
        lengths:  ``[B]`` int32 current token index per slot.
        active:   ``[B]`` int32, nonzero for the running slots.

    Returns ``[B, H, Dp]``; zero for slots that are not active.
    """
    B, H, D = q.shape
    bs, Hkv = k_pool.shape[1:3]
    srows = ks_pool.shape[1]
    rep = H // Hkv
    mb, mw = tables.shape[1], stables.shape[1]
    ct = exact_chunk_tokens(k_pool.shape, k_pool.dtype.itemsize, window)
    cb = ct // bs
    rows = max(ct, srows)
    max_items = mw + -(-window // ct)
    lengths = lengths.astype(jnp.int32)
    # the work list counts items from a length: a slot's items as a length
    items = decode_items(lengths, window=window, chunk_tokens=ct)
    slot, item, n = decode_work_list((items - 1) * ct, active, ct, max_items)
    slot = jnp.where(jnp.arange(slot.shape[0]) < n, slot,
                     slot[jnp.maximum(n - 1, 0)])
    kernel = functools.partial(_decode_kernel, scale=scale, cb=cb, bs=bs,
                               mb=mb, window=window, srows=srows)
    q_g = q.reshape(B, Hkv, rep, D).transpose(0, 2, 1, 3)
    qo_spec = pl.BlockSpec(
        (1, rep, Hkv, D), lambda i, tbl, stbl, lens, sl, it, n: (sl[i], 0, 0,
                                                                 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B * max_items,),
        in_specs=[qo_spec, pool_spec, pool_spec, pool_spec, pool_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, Hkv, D), k_pool.dtype),
            pltpu.VMEM((rows, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, cb)),
            pltpu.VMEM((rep, Hkv, D), F32),
            pltpu.VMEM((rep, Hkv, 128), F32),
            pltpu.VMEM((rep, Hkv, 128), F32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rep, Hkv, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="eva_paged_decode",
    )(tables.astype(jnp.int32), stables.astype(jnp.int32), lengths, slot,
      item, n.reshape(1), q_g, k_pool, v_pool, ks_pool, vs_pool)
    out = o_g.transpose(0, 2, 1, 3).reshape(B, H, D)
    return jnp.where((active > 0)[:, None, None], out, 0)


# -- prefill: a tail's queries by window --------------------------------------

def _q_tile(S: int, window: int) -> int:
    """Query rows a tile: at most a window's, so a tile spans two windows at
    most."""
    t = min(PREFILL_Q_TILE, S, window)
    while t > 8 and S % t:
        t //= 2
    return t if S % t == 0 else S


def prefill_tile_items(start, t, *, ts: int, window: int, kv_rows: int):
    """``(summary items, exact items, first window)`` of query tile ``t`` of
    a tail at ``start``: every window before its last row's, then chunks of
    exact keys from the start of its first row's window to its last row."""
    q0 = start + t * ts
    last = q0 + ts - 1
    w_lo = q0 // window
    return last // window, (last - w_lo * window) // kv_rows + 1, w_lo


def _prefill_kernel(row_ref, srow_ref, start_ref, q_ref, k_hbm, v_hbm, ks_hbm,
                    vs_hbm, o_ref, k_ref, v_ref, sem, acc_ref, m_ref, l_ref,
                    *, scale, bs, mb, window, srows, kv_rows):
    t, i = pl.program_id(0), pl.program_id(1)
    Hkv, rep, ts, _ = q_ref.shape
    cb = kv_rows // bs
    rows = k_ref.shape[0]
    q0 = start_ref[0] + t * ts
    n_sum, n_ex, w_lo = prefill_tile_items(start_ref[0], t, ts=ts,
                                           window=window, kv_rows=kv_rows)
    e = i - n_sum

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        # rows no copy of this tile reaches keep a weight of zero, which
        # does not hide a NaN: what they hold has to be a number
        k_ref[...] = jnp.zeros_like(k_ref)
        v_ref[...] = jnp.zeros_like(v_ref)

    @pl.when(i < n_sum)
    def _summary_block():
        _copy_summary_block(srow_ref[i], ks_hbm, vs_hbm, k_ref, v_ref, sem,
                            srows)

    @pl.when((i >= n_sum) & (i < n_sum + n_ex))
    def _exact_blocks():
        # the blocks up to the tile's last row; the rest of the buffers is at
        # positions no row of the tile may see
        first = w_lo * (window // bs) + e * cb
        _copy_exact_blocks(
            lambda j: row_ref[jnp.minimum(first + j, mb - 1)],
            jnp.minimum((q0 + ts - 1 - w_lo * window) // bs - e * cb + 1, cb),
            k_hbm, v_hbm, k_ref, v_ref, sem, bs)

    @pl.when(i < n_sum + n_ex)
    def _compute():
        dt = k_ref.dtype
        # kv heads lead, so each contraction is a head-batched matmul
        k = jnp.swapaxes(k_ref[...].astype(F32), 0, 1).astype(dt)
        v = jnp.swapaxes(v_ref[...].astype(F32), 0, 1).astype(dt)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (ts, rows), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (ts, rows), 1)
        # a tile spans two windows at most: no vector division
        edge = (w_lo + 1) * window
        q_win = jnp.where(qpos >= edge, w_lo + 1, w_lo)
        kpos = w_lo * window + e * kv_rows + col
        k_win = jnp.where(kpos >= edge, w_lo + 1, w_lo)
        # summary rows count for the rows of later windows; exact rows for
        # the rows of their own window at or behind them.  The item's kind
        # is a scalar: it picks between two integer masks
        summary = (i < n_sum).astype(jnp.int32)
        mask = ((col < jnp.where(i < n_sum, srows, kv_rows))
                & (summary * (i < q_win).astype(jnp.int32)
                   + (1 - summary) * ((kpos <= qpos) & (k_win == q_win)
                                      ).astype(jnp.int32) > 0))[None]
        for r in range(rep):                     # static: H // Hkv
            s = jnp.einsum("gqd,gkd->gqk", q_ref[:, r], k,
                           preferred_element_type=F32,
                           precision=jax.lax.Precision.DEFAULT) * scale
            s = jnp.where(mask, s, NEG_INF)      # [Hkv, ts, rows]
            m_prev = m_ref[:, r, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            # a row that has met no key of its own yet keeps nothing
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[:, r, :, 0:1] * corr + jnp.sum(p, axis=2,
                                                         keepdims=True)
            pv = jnp.einsum("gqk,gkd->gqd", p.astype(dt), v,
                            preferred_element_type=F32,
                            precision=jax.lax.Precision.DEFAULT)
            acc_ref[:, r] = acc_ref[:, r] * corr + pv
            m_ref[:, r] = jnp.broadcast_to(m_new, (Hkv, ts, 128))
            l_ref[:, r] = jnp.broadcast_to(l_new, (Hkv, ts, 128))

    @pl.when(i == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:, :, :, 0:1]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "scale", "interpret"))
def eva_paged_prefill(q, k_pool, v_pool, ks_pool, vs_pool, row, srow, start,
                      *, window: int, scale: float, interpret=False):
    """A tail's attention off both groups of the pool.

    Args:
        q:       ``[S, H, Dp]`` tail queries at positions ``start ..`` (pad
                 lanes zero), in the pools' dtype.
        k_pool, v_pool, ks_pool, vs_pool: as :func:`eva_paged_decode`; the
                 tail's K and V are written and the windows it closes are
                 published.
        row:     ``[max_blocks]`` int32, the slot's exact block row.
        srow:    ``[max_windows]`` int32, the slot's summary block row.
        start:   int32 scalar: position of the first query (a block boundary).

    Returns ``[S, H, Dp]``.
    """
    S, H, D = q.shape
    bs, Hkv = k_pool.shape[1:3]
    srows = ks_pool.shape[1]
    rep = H // Hkv
    mb, mw = row.shape[0], srow.shape[0]
    ts = _q_tile(S, window)
    kv_rows = max(bs, min(PREFILL_KV_ROWS, window) // bs * bs)
    rows = max(kv_rows, srows)
    max_items = mw + (window + ts - 1) // kv_rows + 1
    kernel = functools.partial(_prefill_kernel, scale=scale, bs=bs, mb=mb,
                               window=window, srows=srows, kv_rows=kv_rows)
    q_g = q.astype(k_pool.dtype).transpose(1, 0, 2).reshape(Hkv, rep, S, D)
    qo_spec = pl.BlockSpec((Hkv, rep, ts, D),
                           lambda t, i, row, srow, st: (0, 0, t, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S // ts, max_items),
        in_specs=[qo_spec, pool_spec, pool_spec, pool_spec, pool_spec],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, Hkv, D), k_pool.dtype),
            pltpu.VMEM((rows, Hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, kv_rows // bs)),
            pltpu.VMEM((Hkv, rep, ts, D), F32),
            pltpu.VMEM((Hkv, rep, ts, 128), F32),
            pltpu.VMEM((Hkv, rep, ts, 128), F32),
        ],
    )
    o_g = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, rep, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="eva_paged_prefill",
    )(row.astype(jnp.int32), srow.astype(jnp.int32),
      jnp.asarray(start, jnp.int32).reshape(1), q_g, k_pool, v_pool, ks_pool,
      vs_pool)
    return o_g.reshape(H, S, D).transpose(1, 0, 2)

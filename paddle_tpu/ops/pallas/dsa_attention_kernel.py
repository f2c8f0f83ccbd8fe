"""Pallas TPU kernels of learned sparse attention over the three-sided paged
pool: an *indexer* scores every cached token for a query, the ``topk`` best
are kept, and attention runs over those tokens only.

A layer of such a model caches three things a token: K and V per KV head
(``[num_blocks, block_size, kv_heads, 128]`` each, the K/V kernels' own
operand) and the indexer's one key (``[num_blocks, block_size, 1, lanes]``,
64 numbers in a whole 128-lane row).  For a query ``t`` with indexer heads
``q^I [Hi, Di]`` and head weights ``w [Hi]`` the index score of a cached
token ``s`` is ``I(t, s) = sum_j w_j relu(q^I_j . k^I_s)`` in float32 (the
model folds its constant scales into ``w``), and the query attends to
``S_t``: every ``s <= t`` whose score is at or above the ``topk``-th
largest of its row — all of them while the row is shorter, tokens tied with
the cut all kept.  The pieces, each with a jnp oracle beside it:

- :func:`index_scores` (``dsa_index_scores``): the grid is a work list of
  live (query group, chunk of ``INDEX_CHUNK_TOKENS`` keys) pairs
  (``mla_attention_kernel.decode_work_list``).  A work item copies its
  chunk's live blocks of the indexer pool by block-table *value*, one DMA a
  block — or the whole chunk in one, where its blocks lie one after another
  in the pool, as a long document's do — multiplies the group's ``rows x Hi`` indexer queries with them on
  the MXU, and reduces relu x weight over the heads on the VPU: one
  ``[rows, chunk]`` float32 tile of scores.  Decode: a group is a slot's one
  query.  Tail prefill: a group is ``rows`` consecutive tail queries of the
  one slot.  Places the list does not visit are never written; the caller
  masks them (``s <= t``) before anything reads them.
- the cut is *searched*, not sorted for
  (``ops.threshold_search.kth_largest_key``: 32 counting passes), and
  :func:`select_rows` turns a decode step's ``[slots, T]`` selection mask
  into the list of selected positions with two small matmuls and compares —
  no sort, no scatter, no gather.
- :func:`sparse_decode` (``dsa_sparse_decode``): a work item copies
  ``SELECT_CHUNK_TOKENS`` selected tokens' K and V rows (one DMA a token and
  side, by block table and position) and does one online-softmax update of
  all query heads against them: bytes in proportion to ``topk``, not to the
  context.  The list holds ``topk + TIE_ROOM`` places: more than
  ``TIE_ROOM`` tokens tied *exactly* at the cut are past what a decode step
  keeps (the lowest positions stay); the prefill form and the oracle keep
  every tie.
- :func:`sparse_prefill` (``dsa_sparse_prefill``): the K/V tail-prefill
  kernel itself (``paged_attention_kernel._prefill_kernel``: a work list of
  live (query tile, key chunk) items, a KV head's query heads the rows of one
  matmul, the next item's chunk fetched into a second buffer while this one
  multiplies, a chunk whose blocks are a run of the pool in one copy a side)
  with one more condition in its mask: causal AND ``I >= cut``, the item's
  tile of the scores and its queries' cuts read beside the queries.  It does
  the dense kernel's work whatever is selected; its roofline is counted for
  that.  No second kernel body: what changes the dense tail prefill changes
  this one.

Scores, softmax statistics and accumulators are float32; operands go to the
MXU in the dtype they arrive in (``precision=DEFAULT``: Mosaic refuses bf16
at ``highest``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..threshold_search import key_values, kth_largest_key, order_keys
from .mla_attention_kernel import decode_work_list
from .paged_attention_kernel import _prefill_call, chunk_runs

NEG_INF = -1e30

#: named scopes of the indexer's work in a compiled program's op names: the
#: index scores (and, in the model, the indexer's projections), the cut's
#: search with the list of selected rows, and attention under the selection
INDEX_SCOPE = "dsa.index"
SELECT_SCOPE = "dsa.select"
ATTEND_SCOPE = "dsa.attend"

#: cached keys one work item of ``dsa_index_scores`` scores (whole blocks)
INDEX_CHUNK_TOKENS = 1024
#: tail queries a group of the prefill form of ``dsa_index_scores`` holds
INDEX_GROUP_ROWS = 32
#: selected tokens one work item of ``dsa_sparse_decode`` attends over
SELECT_CHUNK_TOKENS = 512
#: places past ``topk`` in a decode step's list of selected tokens
TIE_ROOM = 128
#: selected tokens whose copies one loop step of ``dsa_sparse_decode`` starts
UNROLL = 8
#: cached tokens one work item of ``dsa_sparse_prefill`` attends over (whole
#: blocks: the dense tail prefill's chunk at the cells' pools) ...
PREFILL_CHUNK_TOKENS = 256
#: ... for this many tail queries (x a KV head's query heads = the rows of an
#: item's matmul: 512 at 8 query heads a KV head, its float32 score tile 2 MB
#: — twice what ``prefill_plan`` gives the dense form; inside the default
#: scoped VMEM with the second chunk buffers, ``tests/test_obs_spans.py``)
PREFILL_Q_TOKENS = 64
#: tail queries whose ``[rows, T]`` index scores exist at once
PREFILL_SCORE_ROWS = 512

_DEFAULT = jax.lax.Precision.DEFAULT


def _whole_blocks(tokens: int, block_size: int, max_blocks: int) -> int:
    return max(1, min(tokens // block_size, max_blocks))


def _pow2_tile(n: int, most: int) -> int:
    """Largest power of two <= ``most`` dividing ``n``; ``n`` if none does."""
    t = 1 << (max(1, min(most, n)).bit_length() - 1)
    while t > 1 and n % t:
        t //= 2
    return t if n % t == 0 else n


def _div(x, d: int):
    """``(x // d, x % d)`` of an int32 vector by a static ``d``: shifts and
    masks for a power of two (Mosaic has no vector integer division)."""
    if d & (d - 1) == 0:
        return x >> (d.bit_length() - 1), x & (d - 1)
    return x // d, x % d


# -- index scores --------------------------------------------------------------

def _index_kernel(tbl_ref, last_ref, grp_ref, chunk_ref, run_ref, q_ref,
                  w_ref, pool_ref, o_ref, k_ref, sem, *, cb, bs, mb, rows,
                  heads):
    i = pl.program_id(0)
    g, c = grp_ref[i], chunk_ref[i]

    # a chunk whose blocks lie one after another in the pool (a document
    # prefilled into a fresh pool does) comes in ONE copy
    @pl.when(run_ref[i] == 1)
    def _one_copy():
        cp = pltpu.make_async_copy(
            pool_ref.at[pl.ds(tbl_ref[g, c * cb], cb)], k_ref, sem.at[0])
        cp.start()
        cp.wait()

    # else one copy a live block of the chunk, by table value; all started,
    # then all awaited.  What the buffer holds past them is past ``last``
    # and is masked by the caller.
    @pl.when(run_ref[i] == 0)
    def _a_copy_a_block():
        def copy(j):
            blk = tbl_ref[g, jnp.minimum(c * cb + j, mb - 1)]
            return pltpu.make_async_copy(pool_ref.at[blk], k_ref.at[j],
                                         sem.at[0])

        def start(j, carry):
            copy(j).start()
            return carry

        def wait(_j, carry):
            # a wait needs the copy's size and semaphore, not its
            # addresses: one fixed descriptor, no table read in the loop
            pltpu.make_async_copy(pool_ref.at[0], k_ref.at[0],
                                  sem.at[0]).wait()
            return carry

        live = jnp.minimum(last_ref[g] // bs - c * cb + 1, cb)
        jax.lax.fori_loop(0, live, start, 0)
        jax.lax.fori_loop(0, live, wait, 0)

    k = k_ref[...].reshape(cb * bs, k_ref.shape[-1])
    s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                            precision=_DEFAULT,
                            preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w_ref[0]                 # [rows * heads, ct]
    if rows == 1:
        o_ref[0] = jnp.sum(s, axis=0, keepdims=True)
    else:
        o_ref[0] = jnp.sum(s.reshape(rows, heads, s.shape[-1]), axis=1)


def _whole_runs(tables, grp, chunk, cb: int):
    """``[items]`` int32: 1 where work item ``(grp, chunk)``'s ``cb`` table
    entries are consecutive block ids (the chunk is one run of the pool)."""
    return chunk_runs(tables, cb)[grp, chunk].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def index_scores(q_idx, w, pool, tables, last, active, *, heads: int,
                 interpret=False):
    """Index scores of groups of queries against their slot's cached keys.

    Args:
        q_idx:  ``[G, rows * heads, Dp]`` indexer queries, a group's rows
                query-major and head-minor, zero in the pad lanes.
        w:      ``[G, rows * heads]`` float32 head weights, scales folded in.
        pool:   ``[num_blocks, block_size, Dp]`` one layer's indexer keys.
        tables: ``[G, max_blocks]`` int32, each group's slot's block row.
        last:   ``[G]`` int32: the last position any query of the group may
                see (chunks past it are not visited).
        active: ``[G]`` int32, nonzero for the groups with a real query.

    Returns:
        ``[G, rows, T]`` float32, ``T = max_blocks * block_size``; only the
        chunks ``0 .. last // chunk`` of an active group are written.
    """
    G, RH, Dp = q_idx.shape
    rows = RH // heads
    bs = pool.shape[1]
    mb = tables.shape[1]
    cb = _whole_blocks(INDEX_CHUNK_TOKENS, bs, mb)
    ct = cb * bs
    max_chunks = -(-mb // cb)
    last = last.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    grp, chunk, n = decode_work_list(last, active, ct, max_chunks)
    kernel = functools.partial(_index_kernel, cb=cb, bs=bs, mb=mb, rows=rows,
                               heads=heads)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, RH, Dp),
                         lambda i, t, la, gr, ch, ru: (gr[i], 0, 0)),
            pl.BlockSpec((1, RH, 1),
                         lambda i, t, la, gr, ch, ru: (gr[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (1, rows, ct), lambda i, t, la, gr, ch, ru: (gr[i], 0, ch[i])),
        scratch_shapes=[pltpu.VMEM((cb, bs, Dp), pool.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, rows, max_chunks * ct),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_index_scores",
    )(tables, last, grp, chunk, _whole_runs(tables, grp, chunk, cb), q_idx,
      w.astype(jnp.float32)[..., None], pool)[..., :mb * bs]


def index_scores_reference(q_idx, w, pool, tables, *, heads: int):
    """The jnp oracle of :func:`index_scores`: every group's whole block row
    gathered contiguous, every position scored."""
    G, RH, Dp = q_idx.shape
    k = jnp.take(pool, tables.reshape(-1), axis=0).reshape(G, -1, Dp)
    s = jnp.einsum("grd,gtd->grt", q_idx, k,
                   preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None]
    return jnp.sum(s.reshape(G, RH // heads, heads, -1), axis=2)


# -- the cut and the selected rows ---------------------------------------------

def selection_cut(scores, visible, topk: int):
    """``(keys, cut)``: the order keys of ``scores [N, T]`` with the places
    that are not ``visible`` at the bottom, and per row the key of its
    ``topk``-th largest visible score (the bottom key for a row with fewer:
    everything visible is then at or above it).  A place is selected when it
    is visible and ``keys >= cut``."""
    keys = order_keys(jnp.where(visible, scores, -jnp.inf))
    return keys, kth_largest_key(keys, topk)


def selection_cut_reference(scores, visible, topk: int):
    """The cut by a full sort: the oracle of :func:`selection_cut`'s search
    (as a float32 value)."""
    z = jnp.where(visible, scores, -jnp.inf)
    return -jnp.sort(-z, axis=-1)[:, min(topk, z.shape[-1]) - 1]


def select_rows(mask, cap: int):
    """The positions a selection mask keeps, in order.

    ``mask [B, T]`` bool -> ``(idx [B, cap] int32, n [B] int32)``: row
    ``b``'s first ``min(count, cap)`` set positions in ``idx[b, :n[b]]``
    (what lies past ``n`` is not a position).  ``T`` is cut into lane rows:
    a place of the list is looked up in its row's running counts, which a
    one-hot product brings to it — small exact integers on the MXU, no
    sort, scatter or gather."""
    B, T = mask.shape
    L = math.gcd(T, 128)
    m = mask.reshape(B, T // L, L).astype(jnp.int32)
    cnt = jnp.sum(m, axis=-1)                               # [B, rows]
    ends = jnp.cumsum(cnt, axis=-1)
    base = ends - cnt
    inside = jnp.cumsum(m, axis=-1)                         # [B, rows, L]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :, None]
    hot = (base[:, None, :] <= j) & (j < ends[:, None, :])  # [B, cap, rows]
    row = jnp.sum(jnp.where(hot, jnp.arange(T // L, dtype=jnp.int32), 0), -1)
    rank = j[..., 0] - jnp.sum(jnp.where(hot, base[:, None, :], 0), -1)
    # counts <= 128 and one-hot weights are exact in bfloat16
    counts = jnp.einsum("bjr,brl->bjl", hot.astype(jnp.bfloat16),
                        inside.astype(jnp.bfloat16), precision=_DEFAULT,
                        preferred_element_type=jnp.float32)
    col = jnp.sum(counts <= rank[..., None].astype(jnp.float32), axis=-1,
                  dtype=jnp.int32)
    n = jnp.minimum(ends[:, -1], cap).astype(jnp.int32)
    idx = jnp.where(j[..., 0] < n[:, None], row * L + col, 0)
    return idx.astype(jnp.int32), n


# -- decode: attention over the selected rows ------------------------------------

def _sparse_decode_kernel(tbl_ref, idx_ref, cnt_ref, slot_ref, chunk_ref,
                          q_ref, k_hbm, v_hbm, o_ref, k_ref, v_ref, sem,
                          acc_ref, m_ref, l_ref, *, scale, st, bs, hkv, rep):
    i = pl.program_id(0)
    b, c = slot_ref[i], chunk_ref[i]
    n = cnt_ref[b]

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # one copy a selected token and side: its K / V row ``[kv_heads, D]``,
    # found by position through the block table
    def copies(j):
        pos = idx_ref[b, c * st + j]
        blk = tbl_ref[b, pos // bs]
        off = pos % bs
        return [pltpu.make_async_copy(pool.at[blk, off], buf.at[j],
                                      sem.at[side])
                for side, (pool, buf) in enumerate(((k_hbm, k_ref),
                                                    (v_hbm, v_ref)))]

    # the copies go UNROLL tokens a loop step (the scalar core's loop
    # overhead is a good part of a 1 KB copy's cost); the list holds position
    # 0 past a slot's count, so the step that runs over the count copies
    # rows the mask drops
    def start(i8, carry):
        for u in range(UNROLL):
            for cp in copies(i8 * UNROLL + u):
                cp.start()
        return carry

    def wait(_i8, carry):
        # a wait needs the copy's size and semaphore, not its addresses
        for _u in range(UNROLL):
            for side, (pool, buf) in enumerate(((k_hbm, k_ref),
                                                (v_hbm, v_ref))):
                pltpu.make_async_copy(pool.at[0, 0], buf.at[0],
                                      sem.at[side]).wait()
        return carry

    live = jnp.minimum(n - c * st, st)
    steps = (live + UNROLL - 1) // UNROLL
    jax.lax.fori_loop(0, steps, start, 0)
    jax.lax.fori_loop(0, steps, wait, 0)

    # rows of the buffers: token-major, kv-head-minor.  Every query head
    # meets every kv head's row; the mask keeps its own group's.
    D = k_ref.shape[-1]
    k = k_ref[...].reshape(st * hkv, D)
    q = q_ref[0]                                       # [H, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_DEFAULT,
                            preferred_element_type=jnp.float32) * scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    tok, g = _div(col, hkv)
    mask = (g == _div(head, rep)[0]) & (tok < live)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0:1] * corr + jnp.sum(p, axis=1, keepdims=True)
    # a weight of zero does not hide a NaN: rows never copied are dropped
    vrow = jax.lax.broadcasted_iota(jnp.int32, (st * hkv, 1), 0)
    v = jnp.where(vrow < live * hkv, v_ref[...].reshape(st * hkv, D), 0)
    pv = jnp.dot(p.astype(v.dtype), v, precision=_DEFAULT,
                 preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(c == (n - 1) // st)                # the slot's last chunk
    def _done():
        l = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_decode(q, k_pool, v_pool, tables, idx, n, active, *, scale: float,
                  interpret=False):
    """One decode step of attention over each slot's selected tokens.

    Args:
        q:       ``[B, H, D]`` current-token queries (``D`` = the pool's
                 lanes).
        k_pool:  ``[num_blocks, block_size, Hkv, D]`` one layer's keys (the
                 current token already written); ``v_pool`` alike.
        tables:  ``[B, max_blocks]`` int32.
        idx:     ``[B, cap]`` int32 selected positions, ``n [B]`` of them
                 (:func:`select_rows`).
        active:  ``[B]`` int32, nonzero for the running slots.

    Returns:
        ``[B, H, D]``; zero for slots that are not active.
    """
    B, H, D = q.shape
    bs, hkv = k_pool.shape[1:3]
    cap = idx.shape[1]
    st = min(SELECT_CHUNK_TOKENS, -(-cap // UNROLL) * UNROLL)
    max_chunks = -(-cap // st)
    pad = max_chunks * st - cap
    if pad:
        idx = jnp.pad(idx, ((0, 0), (0, pad)))
    n = n.astype(jnp.int32)
    live = (active > 0) & (n > 0)
    slot, chunk, items = decode_work_list(n - 1, live, st, max_chunks)
    kernel = functools.partial(_sparse_decode_kernel, scale=scale, st=st,
                               bs=bs, hkv=hkv, rep=H // hkv)
    qo_spec = pl.BlockSpec((1, H, D),
                           lambda i, t, ix, cn, sl, ch: (sl[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(items,),
        in_specs=[qo_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((st, hkv, D), k_pool.dtype),
            pltpu.VMEM((st, hkv, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((H, D), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dsa_sparse_decode",
    )(tables.astype(jnp.int32), idx, n, slot, chunk, q, k_pool, v_pool)
    return jnp.where(live[:, None, None], out, 0)


def masked_decode_reference(q, k_pool, v_pool, tables, selected, active, *,
                            scale: float):
    """The jnp oracle of :func:`sparse_decode`: every slot's block row
    gathered contiguous, one softmax under ``selected [B, T]``."""
    B, H, D = q.shape
    hkv = k_pool.shape[2]

    def rows(pool):
        return jnp.take(pool, tables.reshape(-1), axis=0
                        ).reshape(B, -1, hkv, D)

    qg = q.reshape(B, hkv, H // hkv, D)
    s = jnp.einsum("bgrd,btgd->bgrt", qg, rows(k_pool),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(selected[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bgrt,btgd->bgrd", p, rows(v_pool),
                     preferred_element_type=jnp.float32).astype(q.dtype)
    live = (active > 0) & jnp.any(selected, axis=-1)
    return jnp.where(live[:, None, None], out.reshape(B, H, D), 0)


# -- tail prefill: the dense kernel under causal AND selected --------------------

def sparse_prefill_plan(S: int, block_size: int, max_blocks: int):
    """``(tile, chunk_tokens)`` of the tail-prefill kernel under a selection,
    for ``S`` tail queries: from the shapes alone (the engine's host counts
    its ``prefill_items_*`` with it; a bucket and the ``PREFILL_SCORE_ROWS``
    queries of it that one call takes have the same tile)."""
    return (_pow2_tile(S, PREFILL_Q_TOKENS),
            _whole_blocks(PREFILL_CHUNK_TOKENS, block_size, max_blocks)
            * block_size)


def sparse_prefill(q, k_pool, v_pool, block_row, start, length, scores, cut,
                   *, scale: float, interpret=False):
    """Tail-prefill attention off the block pool under causal AND selected:
    the K/V tail-prefill kernel (``paged_attention_kernel._prefill_call``: a
    work list of live (query tile, key chunk) items, the next item's chunk
    fetched while this one multiplies, a run of the pool in one copy) with
    the selection as one more condition of its mask.

    Args:
        q:         ``[S, H, D]`` tail queries at absolute positions
                   ``start .. start+S-1`` (``D`` = the pool's lanes).
        k_pool:    ``[num_blocks, block_size, Hkv, D]`` one layer's keys (the
                   tail already written); ``v_pool`` alike.
        block_row: ``[max_blocks]`` int32, the slot's row of the table.
        start:     int32 scalar: the first query's position.
        length:    int32 scalar: the prompt's real length; tiles wholly at
                   or past it are not visited.
        scores:    ``[S, T]`` float32 index scores of every tail query
                   (places past a query's position may hold anything).
        cut:       ``[S]`` float32: a query keeps ``scores >= cut``.

    Returns:
        ``[S, H, D]``; zero in the rows at or past ``length``.
    """
    ts, ct = sparse_prefill_plan(q.shape[0], k_pool.shape[1],
                                 block_row.shape[0])
    return _prefill_call(
        q[None], k_pool, v_pool, block_row,
        jnp.asarray(start, jnp.int32).reshape(()),
        jnp.asarray(length, jnp.int32).reshape(()), tile=ts, chunk_tokens=ct,
        window=0, interpret=interpret, scores=scores, cut=cut[:, None],
        scale=scale)[0]


def masked_prefill_reference(q, k_pool, v_pool, block_row, selected, *,
                             scale: float):
    """The jnp oracle of :func:`sparse_prefill` (computes the pad rows too):
    the slot's block row gathered contiguous, one softmax under
    ``selected [S, T]`` (causal already in it)."""
    S, H, D = q.shape
    hkv = k_pool.shape[2]
    k = jnp.take(k_pool, block_row, axis=0).reshape(-1, hkv, D)
    v = jnp.take(v_pool, block_row, axis=0).reshape(-1, hkv, D)
    qg = q.reshape(S, hkv, H // hkv, D)
    s = jnp.einsum("sgrd,tgd->sgrt", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(selected[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("sgrt,tgd->sgrd", p, v,
                      preferred_element_type=jnp.float32
                      ).astype(q.dtype).reshape(S, H, D)


# -- the two paths a cache context calls -----------------------------------------

def _visible(T: int, last):
    """``[N, T]`` bool: positions ``0 .. last[n]`` of each row."""
    return jnp.arange(T, dtype=jnp.int32)[None, :] <= last[:, None]


def indexed_decode(q, q_idx, w, k_pool, v_pool, i_pool, tables, lengths,
                   active, *, topk: int, heads: int, scale: float,
                   kernel: str, interpret=False):
    """One decode step through the indexer: ``(out [B, H, D], selected [B]
    int32)`` — scores of each active slot's query against its cached keys,
    the cut, the selected rows, attention over them.  ``kernel="reference"``
    is the oracle: gathers, a sort for the cut, one masked softmax."""
    B, T = tables.shape[0], tables.shape[1] * i_pool.shape[1]
    lengths = lengths.astype(jnp.int32)
    visible = _visible(T, lengths) & (active > 0)[:, None]
    if kernel != "pallas":
        s = index_scores_reference(q_idx, w, i_pool, tables, heads=heads)
        cut = selection_cut_reference(s[:, 0], visible, topk)
        selected = visible & (s[:, 0] >= cut[:, None])
        out = masked_decode_reference(q, k_pool, v_pool, tables, selected,
                                      active, scale=scale)
        return out, jnp.sum(selected, axis=-1, dtype=jnp.int32)
    with jax.named_scope(INDEX_SCOPE):
        s = index_scores(q_idx, w, i_pool, tables, lengths, active,
                         heads=heads, interpret=interpret)
    with jax.named_scope(SELECT_SCOPE):
        keys, cut = selection_cut(s[:, 0], visible, topk)
        idx, n = select_rows(visible & (keys >= cut[:, None]),
                             topk + TIE_ROOM)
    with jax.named_scope(ATTEND_SCOPE):
        out = sparse_decode(q, k_pool, v_pool, tables, idx, n, active,
                            scale=scale, interpret=interpret)
    return out, n


def indexed_prefill(q, q_idx, w, k_pool, v_pool, i_pool, block_row, start,
                    length, *, topk: int, heads: int, scale: float,
                    kernel: str, interpret=False):
    """Tail prefill through the indexer: ``q [S, H, D]``, ``q_idx [S, heads,
    Dp]``, ``w [S, heads]`` at positions ``start ..`` -> ``[S, H, D]``
    (``scale``: the softmax scale, the head's own width ``** -0.5``).
    The tail is taken ``PREFILL_SCORE_ROWS`` queries at a time, so that the
    index scores that exist at once are ``[rows, T]`` and never ``[S, T]`` of
    a long bucket."""
    S = q.shape[0]
    T = block_row.shape[0] * i_pool.shape[1]
    start = jnp.asarray(start, jnp.int32).reshape(())
    length = jnp.asarray(length, jnp.int32).reshape(())
    ts = _pow2_tile(S, PREFILL_SCORE_ROWS)
    rows = _pow2_tile(ts, INDEX_GROUP_ROWS)
    G = ts // rows

    def tile(args):
        q_t, qi_t, w_t, q0 = args
        qpos = q0 + jnp.arange(ts, dtype=jnp.int32)
        visible = _visible(T, qpos)
        if kernel != "pallas":
            s = index_scores_reference(
                qi_t.reshape(1, ts * heads, -1), w_t.reshape(1, -1), i_pool,
                block_row[None], heads=heads)[0]
            cut = selection_cut_reference(s, visible, topk)
            return masked_prefill_reference(
                q_t, k_pool, v_pool, block_row,
                visible & (s >= cut[:, None]), scale=scale)
        last = q0 + (jnp.arange(G, dtype=jnp.int32) + 1) * rows - 1
        with jax.named_scope(INDEX_SCOPE):
            s = index_scores(
                qi_t.reshape(G, rows * heads, -1),
                w_t.reshape(G, rows * heads), i_pool,
                jnp.broadcast_to(block_row, (G,) + block_row.shape), last,
                (last - rows + 1 < length).astype(jnp.int32), heads=heads,
                interpret=interpret).reshape(ts, T)
        with jax.named_scope(SELECT_SCOPE):
            _keys, cut = selection_cut(s, visible, topk)
        with jax.named_scope(ATTEND_SCOPE):
            return sparse_prefill(q_t, k_pool, v_pool, block_row, q0, length,
                                  s, key_values(cut), scale=scale,
                                  interpret=interpret)

    q0s = start + jnp.arange(S // ts, dtype=jnp.int32) * ts
    parts = (q.reshape(S // ts, ts, *q.shape[1:]),
             q_idx.reshape(S // ts, ts, *q_idx.shape[1:]),
             w.reshape(S // ts, ts, heads), q0s)
    if S == ts:
        return tile(tuple(p[0] for p in parts))
    return jax.lax.map(tile, parts).reshape(q.shape)

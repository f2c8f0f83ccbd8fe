"""Pallas TPU flash attention: fused causal attention fwd + bwd kernels.

Reference parity: fused_attention_op.cu / fmha_ref.h (the reference's
hand-fused CUDA attention) — re-designed as a blocked online-softmax kernel
for the MXU (never materializes the [S, S] score matrix in HBM).

Layout: kernels run on [BH, S, D] (batch×heads flattened); the public entry
takes paddle's fused-attention layout [B, S, H, D].

One plan (:func:`flash_plan`) sizes all three kernels from what the call
shows (``S``, ``head_dim``, the operands' bytes).  A grid step owns one
``block_q``-row block — of queries in the forward and in ``bwd_dq``, of
keys in ``bwd_dkv`` — and is handed the same rows of the other operand:
the whole sequence where that fits VMEM (one grid step a head), else a
power-of-two slice of it on the grid's third axis.  Inside the step the
kernel walks the block pair in ``sub``-row pieces, and **the walk ends at
the causal diagonal** (:func:`_walk`): a sub-block meets only the rows of
the other side that lie on or under the diagonal, as ONE matmul tile whose
width is that many rows, and only the ``sub × sub`` square the diagonal
crosses builds the iota / compare / select.  A block pair wholly above the
diagonal is skipped, and its ``index_map`` is clamped to the nearest live
pair so no copy is issued for it; one wholly under it walks every piece
unmasked, as a call that is not causal does.

The walk is unrolled when the kernel is traced.  A trip count that is a
scalar of the grid step (``lax.fori_loop`` over 512 x 256 tiles, then a
work list of live tiles under scalar prefetch) was built first and timed
on the chip, and lost to the grid it replaced: with the score tile held
``[queries, keys]`` every trip paid two cross-lane reductions and five
``[rows, 1]`` column operations a row group — 1.7 us a 512-row trip before
its first multiply, ~1.0 ms a call for each pass over the rows whatever
the tile's width (``PERF.md`` §6, PR 31).  So the forward and ``bwd_dkv``
hold the tile ``[keys, queries]``: the queries lie along the lanes, the
softmax's running max / sum (f32, VMEM scratch) are ``[1, rows]`` lane-
dense rows, its reductions run down the sublanes, and lse / delta
broadcast along them for nothing.  And the sizes are static, so the trips
are too: straight-line code the compiler overlaps tile with tile.

Backward is the standard two-kernel recomputation from (q, k, v, O, lse,
delta=rowsum(dO·O)): one for (dk, dv), one for dq; a step that sees the
whole sequence writes them straight out, else f32 VMEM accumulators add
them up over the grid's third axis.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs import spans as _spans

NEG_INF = -1e30

# The kernels' names.  XLA:TPU names a kernel's custom call, and with it the
# event of a profiler trace, after the LAST part of its op_name: the
# ``pallas_call``'s ``name=`` where there is one, else the innermost scope
# (``attention.pallas_flash`` for a forward pass, ``jvp(...)`` /
# ``transpose(jvp(...))`` of it under autograd, parentheses read as ``_``).
# The benchmark's accepted ``flash_roofline`` finds the kernels by those
# scope-derived instruction names (``benchmarks/kernel_costs/
# flash_attention.py``: anchored on ``attention.pallas_flash`` and on
# ``transpose_jvp_attention.pallas_flash``), so each name is one of them
# made longer, never another word: the backward kernels only ever run as
# the transpose of the forward's jvp.
FWD_NAME = "attention.pallas_flash.fwd"
BWD_DKV_NAME = "transpose_jvp_attention.pallas_flash.bwd_dkv"
BWD_DQ_NAME = "transpose_jvp_attention.pallas_flash.bwd_dq"

# MXU precision for the kernel's dot_generals.  bf16 operands are exact on
# the MXU with f32 accumulation, and Mosaic rejects the fp32 ("highest")
# contract precision for bf16 lhs ("Bad lhs type"), so pin DEFAULT there;
# f32 operands defer to the global jax_default_matmul_precision (tests set
# "highest" for the f32-shadow oracle comparisons).
def _precision_for(dtype):
    return (jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None)


def _dot(a, b, contract_a, contract_b):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=_precision_for(a.dtype))


# -- the plan --------------------------------------------------------------

# Tuned on v5e at GPT-2 345M's call, [256, 1024, 64] bf16 causal, each
# kernel alone by its device events (ms a call: forward / bwd_dkv / bwd_dq;
# the grid of 512 x 1024 blocks this replaced: 1.33 / 1.64 / 1.24).  One
# step a head: sub 128: 0.79 / 1.02 / 0.81; 256: 0.78 / 0.97 / 0.81; 512:
# 0.73 / 1.11 / 0.85; 1024 (no skip): 0.85 / 1.45 / 1.10.  Two 512-row
# blocks a head, sub 256: 1.09 / 1.46 / 1.50; four of 256: 2.19 / 2.41 /
# 2.58 — a grid step costs more than the squares it saves.  Several blocks
# a head, 16,384 tokens a call (the replaced grid | 1024-row blocks of sub
# 256 | of sub 512 | 512-row blocks of sub 256): S 2048 x 64 lanes 2.31 /
# 2.80 / 2.43 | 1.36 / 1.93 / 1.65 | 1.30 / 2.04 / 1.78 | 2.00 / 2.41 /
# 2.39; 4096 x 64: 3.87 / 4.87 / 4.19 | 2.56 / 3.50 / 3.04 | 2.50 / 3.56 /
# 3.17 | 3.37 / 4.36 / 3.89; 2048 x 128: 1.12 / 1.34 / 1.16 | 0.77 / 0.95 /
# 0.78; 4096 x 128: 1.86 / 2.28 / 2.01 | 1.41 / 1.74 / 1.45.
PREFERRED_SUB = 256
# Rows of a block at 2-byte operands of up to 128 lanes; wider elements or
# heads take fewer (f32 in the parity tests: 512), narrower ones no more:
# most of what fills VMEM is float32 whatever the operands.  First to fill it
# is ``bwd_dkv``: q, k, v, dO and the two outputs in whole 128-lane rows,
# lse and delta as [rows, 1] f32 blocks (512 B a row each), all double-
# buffered, two [rows, head_dim] f32 accumulators and four f32 tiles of
# ``sub`` x rows — about 11 KB a row, of the 16 MB a kernel may use by
# default.  (Under a 64 MB limit 2048-row blocks ran S = 2048 and 4096
# another 12–22 % faster: ROADMAP S2, not shipped.)
BLOCK_ROWS = 1024


class FlashPlan(NamedTuple):
    """Sizes of one call, and what its walk visits, in ``sub × sub``
    squares of the ``S × S`` scores (the three kernels visit the same)."""
    block_q: int       # rows a grid step owns, and is handed of the other
    sub: int           # rows a piece of the walk takes
    n_blocks: int      # blocks a head: the grid's last two axes
    tiles_total: int
    tiles_visited: int
    tiles_masked: int

    @property
    def n_sub(self):           # sub-blocks a block
        return self.block_q // self.sub


def _dividing_block(S, preferred):
    """Largest power-of-two block ≤ preferred that divides S (S itself
    where it is smaller), so the supported shape set never shrinks with
    the preferred sizes — S=768/1536 etc. run on smaller tiles."""
    b = min(preferred, S)
    while b > 8 and S % b:
        b //= 2
    return b


@functools.lru_cache(maxsize=None)
def flash_plan(S, head_dim, itemsize, causal=True, block_q=None,
               sub=None) -> FlashPlan:
    """The one place the three kernels' sizes come from: what the call
    shows (sequence, head width, the operands' bytes) decides them.
    ``block_q`` / ``sub`` pin a size, for tests and for timing a size
    alone."""
    if block_q is None:
        row_bytes = itemsize * -(-head_dim // 128)
        block_q = _dividing_block(
            S, min(BLOCK_ROWS, BLOCK_ROWS * 2 // row_bytes))
    if sub is None:
        sub = _dividing_block(block_q, PREFERRED_SUB)
        if sub % 128:
            sub = block_q   # a piece is whole 128-lane columns, or the block
    if S % block_q or block_q % sub:
        raise ValueError(
            f"sequence {S} must divide into blocks of {block_q}, a block "
            f"into sub-blocks of {sub}")
    total = visited = masked = 0
    n, n_blocks = block_q // sub, S // block_q
    for i in range(n_blocks):
        for im in range(n_blocks):
            total += n * n
            if causal and im > i:
                continue
            diagonal = causal and im == i
            for _, first in _walk(n, diagonal):
                visited += n - first
                masked += diagonal
    return FlashPlan(block_q, sub, n_blocks, total, visited, masked)


def _walk(n, diagonal):
    """The walk of one block pair of ``n × n`` squares, as ``(j, first)``:
    sub-block ``j`` of the walked side meets the sub-blocks ``first .. n-1``
    of the other side in one tile, and on a pair the ``diagonal`` runs
    through, the first of them is the square it crosses (the only one that
    is masked).  There the walked side is the keys and the other the
    queries (a key meets the queries from its own on; ``bwd_dq`` walks the
    queries and reads the same pairs from the other end: query sub-block
    ``j`` meets the keys ``0 .. j``); a pair wholly under the diagonal, and
    every pair of a call that is not causal, meets everything unmasked."""
    return [(j, j if diagonal else 0) for j in range(n)]


def _for_pair(i, im, plan, causal, keys_own, body):
    """Trace ``body(diagonal)`` for the grid step that owns block ``i`` and
    is handed block ``im`` of the other operand: the diagonal walk where
    they are the same rows, the full one where the pair lies wholly under
    the diagonal (``keys_own``: the owned block is of keys, so under means
    ``im > i``), nothing where it lies above."""
    if not causal:
        body(False)
    elif plan.n_blocks == 1:
        body(True)
    else:
        pl.when(im == i)(lambda: body(True))
        pl.when(im > i if keys_own else im < i)(lambda: body(False))


def _scores(q, k, *, scale, masked, keys_first):
    """Scaled scores s = qk^T·scale in f32, as ``[keys, queries]`` or as
    ``[queries, keys]``; ``masked``: the tile's square on the diagonal (its
    first queries against all its keys, or its last keys against all its
    queries: both sides start at the same row of the sequence) keeps what
    is on or under it — shared by fwd and both bwd kernels so the
    mask/scale math cannot diverge."""
    a, b = (k, q) if keys_first else (q, k)
    s = _dot(a, b, 1, 1) * scale
    if masked:
        side = a.shape[0]
        rest, square = ((s[:, side:], s[:, :side]) if keys_first
                        else (s[:, :-side], s[:, -side:]))
        query = jax.lax.broadcasted_iota(jnp.int32, square.shape,
                                         int(keys_first))
        key = jax.lax.broadcasted_iota(jnp.int32, square.shape,
                                       int(not keys_first))
        square = jnp.where(query >= key, square, NEG_INF)
        if rest.shape[1]:
            s = jnp.concatenate(
                [square, rest] if keys_first else [rest, square], axis=1)
        else:
            s = square
    return s


def _p_ds(q, k, v, do, lse, delta, *, scale, masked, keys_first):
    """Recompute (p, ds) for the backward kernels: p = exp(s − lse),
    ds = p ∘ (dO·vᵀ − delta)·scale; ``keys_first`` as in :func:`_scores`,
    with lse and delta as ``[1, queries]`` rows (else ``[queries, 1]``)."""
    s = _scores(q, k, scale=scale, masked=masked, keys_first=keys_first)
    p = jnp.exp(s - lse)
    a, b = (v, do) if keys_first else (do, v)
    ds = p * (_dot(a, b, 1, 1) - delta) * scale
    return p, ds


def _as_row(col):
    """A ``[N, 1]`` column as the ``[1, N]`` row."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[0:1, :]


def _as_col(row):
    """A ``[1, N]`` row as the ``[N, 1]`` column."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, 0:1]


def _put(out_ref, acc_ref, rows, value):
    """A piece of a backward result: straight out where the step sees the
    whole sequence (there is no accumulator), else added up over the steps."""
    if acc_ref is None:
        out_ref[0, rows, :] = value.astype(out_ref.dtype)
    else:
        acc_ref[rows, :] += value


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, plan):
    iq, ik = pl.program_id(1), pl.program_id(2)
    sub = plan.sub

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def walk(diagonal):
        # ascending: a query's first tile holds key 0, which every query
        # sees, before any tile it sees nothing of
        for j, first in _walk(plan.n_sub, diagonal):
            keys = slice(j * sub, (j + 1) * sub)
            queries = slice(first * sub, None)
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]          # [SUB, D]
            s = _scores(q_ref[0, queries, :], k, scale=scale,
                        masked=diagonal, keys_first=True)        # [SUB, Q]
            m_prev = m_ref[:, queries]                           # [1, Q]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)                               # [SUB, Q]
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, queries] = (l_ref[:, queries] * corr
                                 + jnp.sum(p, axis=0, keepdims=True))
            acc_ref[:, queries] = (acc_ref[:, queries] * corr
                                   + _dot(v, p.astype(v.dtype), 0, 0))
            m_ref[:, queries] = m_new

    _for_pair(iq, ik, plan, causal, False, walk)

    @pl.when(ik == plan.n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).T.astype(o_ref.dtype)      # [BQ, D]
        lse_ref[0] = _as_col(m_ref[...] + jnp.log(jnp.maximum(l, 1e-30)))


def _specs(plan, D, causal, keys_own=False):
    """``(own, other, own_rows, other_rows)``: BlockSpecs of an operand of
    the block a grid step owns and of one it is handed block by block,
    ``[.., D]`` wide and ``[.., 1]`` wide (lse, delta).  A pair above the
    diagonal asks for the nearest live pair's block — the one already in
    VMEM, so a dead step copies nothing."""
    def other_map(bh, i, im):
        if causal:
            im = jnp.maximum(im, i) if keys_own else jnp.minimum(im, i)
        return (bh, im, 0)

    def own_map(bh, i, im):
        return (bh, i, 0)

    return (pl.BlockSpec((1, plan.block_q, D), own_map),
            pl.BlockSpec((1, plan.block_q, D), other_map),
            pl.BlockSpec((1, plan.block_q, 1), own_map),
            pl.BlockSpec((1, plan.block_q, 1), other_map))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd(q, k, v, *, scale, causal, plan, interpret):
    BH, S, D = q.shape
    own, other, own_rows, _ = _specs(plan, D, causal)
    # NOTE on the lse layout: the kernel-facing buffer is [BH, S, 1] (the
    # only legal minor-dim block shape here), which HBM-pads 128x under
    # T(8,128).  The caller immediately slices it to a compact [BH, S]
    # residual so the padded form is transient, not saved (it was 127MB of
    # pure padding per layer at S=1024, BH=256 — the round-2 OOM culprit).
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, plan=plan),
        grid=(BH, plan.n_blocks, plan.n_blocks),
        in_specs=[own, other, other],
        out_specs=[own, own_rows],
        scratch_shapes=[
            pltpu.VMEM((D, plan.block_q), jnp.float32),
            pltpu.VMEM((1, plan.block_q), jnp.float32),
            pltpu.VMEM((1, plan.block_q), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=FWD_NAME,
    )(q, k, v)
    return o, lse[:, :, 0]


# -- backward --------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *acc, scale, causal, plan):
    ik, iq = pl.program_id(1), pl.program_id(2)
    sub = plan.sub
    dk_acc, dv_acc = acc or (None, None)

    if acc:
        @pl.when(iq == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    def walk(diagonal):
        lse, delta = _as_row(lse_ref[0]), _as_row(delta_ref[0])  # [1, BQ]
        for j, first in _walk(plan.n_sub, diagonal):
            keys = slice(j * sub, (j + 1) * sub)
            queries = slice(first * sub, None)
            q, do = q_ref[0, queries, :], do_ref[0, queries, :]  # [Q, D]
            p, ds = _p_ds(q, k_ref[0, keys, :], v_ref[0, keys, :], do,
                          lse[:, queries], delta[:, queries], scale=scale,
                          masked=diagonal, keys_first=True)      # [SUB, Q]
            # dv += p @ dO ; dk += ds @ q
            _put(dv_ref, dv_acc, keys, _dot(p.astype(do.dtype), do, 1, 0))
            _put(dk_ref, dk_acc, keys, _dot(ds.astype(q.dtype), q, 1, 0))

    _for_pair(ik, iq, plan, causal, True, walk)

    if acc:
        @pl.when(iq == plan.n_blocks - 1)
        def _finalize():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *acc, scale, causal, plan):
    iq, ik = pl.program_id(1), pl.program_id(2)
    sub = plan.sub
    (dq_acc,) = acc or (None,)

    if acc:
        @pl.when(ik == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def walk(diagonal):
        # the same pairs read from the queries' end: query sub-block j
        # meets the keys up to its own (all of them off the diagonal)
        for j, _ in _walk(plan.n_sub, diagonal):
            queries = slice(j * sub, (j + 1) * sub)
            keys = slice(0, (j + 1) * sub if diagonal else None)
            k = k_ref[0, keys, :]                                # [K, D]
            _, ds = _p_ds(q_ref[0, queries, :], k, v_ref[0, keys, :],
                          do_ref[0, queries, :], lse_ref[0, queries, :],
                          delta_ref[0, queries, :], scale=scale,
                          masked=diagonal, keys_first=False)     # [SUB, K]
            _put(dq_ref, dq_acc, queries, _dot(ds.astype(k.dtype), k, 1, 0))

    _for_pair(iq, ik, plan, causal, False, walk)

    if acc:
        @pl.when(ik == plan.n_blocks - 1)
        def _finalize():
            dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd(res, g, *, scale, causal, plan, interpret):
    q, k, v, o, lse = res
    do = g
    BH, S, D = q.shape
    lse = lse[:, :, None]        # compact residual -> kernel-facing [BH,S,1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                     # [BH, S, 1]
    # f32 accumulators over the grid's third axis; a step that sees the
    # whole sequence writes its results straight out and has none
    acc = [] if plan.n_blocks == 1 else [
        pltpu.VMEM((plan.block_q, D), jnp.float32)]

    # bwd_dkv owns a key block; q, dO, lse and delta come block by block
    own, other, _, other_rows = _specs(plan, D, causal, keys_own=True)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          plan=plan),
        grid=(BH, plan.n_blocks, plan.n_blocks),
        in_specs=[other, own, own, other, other_rows, other_rows],
        out_specs=[own, own],
        scratch_shapes=acc * 2,
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=BWD_DKV_NAME,
    )(q, k, v, do, lse, delta)

    # bwd_dq owns a query block; k and v come block by block
    own, other, own_rows, _ = _specs(plan, D, causal)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          plan=plan),
        grid=(BH, plan.n_blocks, plan.n_blocks),
        in_specs=[own, other, other, own, own_rows, own_rows],
        out_specs=own,
        scratch_shapes=acc,
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=BWD_DQ_NAME,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# -- public entry (custom_vjp over [B, S, H, D]) ---------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, plan, interpret):
    o, _ = _fwd(q, k, v, scale=1.0 / math.sqrt(q.shape[-1]), causal=causal,
                plan=plan, interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, plan, interpret):
    o, lse = _fwd(q, k, v, scale=1.0 / math.sqrt(q.shape[-1]), causal=causal,
                  plan=plan, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, plan, interpret, res, g):
    scale = 1.0 / math.sqrt(res[0].shape[-1])
    return _bwd(res, g, scale=scale, causal=causal, plan=plan,
                interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)
# jitted so that the unrolled walks are traced once a shape and not once a
# layer and pass of the program that calls them (PR 27's lesson: 24 layers x
# the build's passes)
_flash_traced_once = jax.jit(_flash, static_argnums=(3, 4, 5))


def flash_attention_fused(q, k, v, causal=True, block_q=None, block_k=None,
                          interpret=False):
    """q/k/v: [B, S, H, D] → [B, S, H, D].  ``block_q`` / ``block_k`` (the
    walk's sub-block) pin a size of :func:`flash_plan`."""
    B, S, H, D = q.shape
    if k.shape[1] != S:
        raise ValueError(
            f"flash_attention_fused requires Sq == Sk (self-attention); got "
            f"q seq {S}, k seq {k.shape[1]} — use the XLA oracle for "
            f"cross-attention/decode")
    plan = flash_plan(S, D, q.dtype.itemsize, causal, block_q, block_k)
    # the engagement share of the causal skip, once a traced call
    _spans.mark("attention.flash_plan", seq=S, head_dim=D,
                causal=int(causal), block_q=plan.block_q, sub=plan.sub,
                tiles_total=plan.tiles_total,
                tiles_visited=plan.tiles_visited,
                tiles_masked=plan.tiles_masked)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    o = _flash_traced_once(to_bh(q), to_bh(k), to_bh(v), causal, plan,
                           interpret)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def supports(q_shape, k_shape, block_q=None, block_k=None) -> bool:
    """Dispatch guard: shapes this kernel handles (self-attention, block-
    divisible sequence), whatever the operands' bytes: the guard sees no
    dtype, and a sequence that 4-byte operands' blocks (the smallest)
    divide, wider blocks divide too."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    S = q_shape[1]
    if k_shape[1] != S:
        return False
    try:
        flash_plan(S, q_shape[3], 4, True, block_q, block_k)
    except ValueError:
        return False
    return True

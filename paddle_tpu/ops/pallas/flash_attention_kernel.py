"""Pallas TPU flash attention: fused causal attention fwd + bwd kernels.

Reference parity: fused_attention_op.cu / fmha_ref.h (the reference's
hand-fused CUDA attention) — re-designed as a blocked online-softmax kernel
for the MXU (never materializes the [S, S] score matrix in HBM).

Layout: a head reaches a kernel, and leaves it, **feature-major**:
``[head_dim, S]``, ``head_dim`` on the sublanes (64 or 128 rows: whole tiles
of bf16 and of f32), the sequence on the lanes, nothing padded.  The
kernel-facing arrays are ``[B, F, S]`` with a head a block of ``head_dim``
rows — the order in which XLA:TPU lays a projection's ``[B, S, F]`` output
out anyway (the sequence minor), so the logical transpose costs nothing and
no XLA op stands between a projection's matmul and a kernel.  Two entries
over the same kernel bodies, differing in ``BlockSpec`` index maps only:
:func:`flash_attention_fused_qkv` for a caller that holds a head-major fused
projection ``[B, S, H, 3·D]`` (GPT: head ``n``'s q, k, v are the row blocks
``3n``, ``3n + 1``, ``3n + 2`` of ONE array, three specs over it, no split;
the gradient is written back as one such array, ``bwd_dkv`` a head's k and v
rows and ``bwd_dq``, aliased onto its result, the q rows: no concatenate),
and :func:`flash_attention_fused` with paddle's fused-attention signature
``[B, S, H, D]`` for a caller that holds three tensors.  Which one runs is
what the caller holds, never a flag.

One plan (:func:`flash_plan`) sizes all three kernels from what the call
shows (``S``, ``head_dim``, the operands' bytes).  A grid step owns one
``block_q``-column block — of queries in the forward and in ``bwd_dq``, of
keys in ``bwd_dkv`` — and is handed the same columns of the other operand:
the whole sequence where that fits VMEM (one grid step a head), else a
power-of-two slice of it on the grid's last axis.  Inside the step the
kernel walks the block pair in ``sub``-column pieces of keys, and **the walk
ends at the causal diagonal** (:func:`_walk`): a sub-block of keys meets only
the queries that lie on or under the diagonal, as ONE matmul tile whose
width is that many queries, and only the ``sub × sub`` square the diagonal
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
the tile's width (``PERF.md`` §6, PR 31).  So all three kernels hold the
tile ``[keys, queries]``: ``s[K, Q] = kᵀq`` over ``head_dim`` (the key
piece ``[D, sub]`` is the one operand transposed, once a piece),
``acc[D, Q] += v p``, ``dv[D, K] = dO pᵀ`` and ``dk[D, K] = q dsᵀ`` over the
queries, ``dq[D, Q] += k ds``; the queries lie along the lanes, the
softmax's running max / sum, lse and delta are ``[1, queries]`` lane-dense
rows (f32), reductions run down the sublanes and the rows broadcast along
them for nothing.  And the sizes are static, so the trips are too:
straight-line code the compiler overlaps tile with tile.

Backward is the standard two-kernel recomputation from (q, k, v, O, dO,
lse): one for (dk, dv), one for dq.  **lse leaves the forward as
``[B·H, 1, S]`` float32** (the residual, 4 KB a head), and **delta =
rowsum(dO·O) is computed in the backward kernels** from the dO and O blocks
they hold (a multiply and a sum down ``head_dim`` sublanes): no
``[.., S, 1]`` array exists anywhere.  ``bwd_dkv`` writes a piece straight
out where a step sees the whole sequence, else f32 VMEM accumulators add the
pieces up over the grid's last axis; ``bwd_dq`` walks the keys as the other
two do and adds a head's dq up in VMEM.
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
# kernel alone by its device events (ms a call: forward / bwd_dkv / bwd_dq),
# on PR 31's row-major kernels (the grid of 512 x 1024 blocks they replaced:
# 1.33 / 1.64 / 1.24).  One step a head: sub 128: 0.79 / 1.02 / 0.81; 256:
# 0.78 / 0.97 / 0.81; 512: 0.73 / 1.11 / 0.85; 1024 (no skip): 0.85 / 1.45 /
# 1.10.  Two 512-row blocks a head, sub 256: 1.09 / 1.46 / 1.50; four of
# 256: 2.19 / 2.41 / 2.58 — a grid step costs more than the squares it
# saves.  Several blocks a head, 16,384 tokens a call (the replaced grid |
# 1024-row blocks of sub 256 | of sub 512 | 512-row blocks of sub 256):
# S 2048 x 64 lanes 2.31 / 2.80 / 2.43 | 1.36 / 1.93 / 1.65 | 1.30 / 2.04 /
# 1.78 | 2.00 / 2.41 / 2.39; 4096 x 64: 3.87 / 4.87 / 4.19 | 2.56 / 3.50 /
# 3.04 | 2.50 / 3.56 / 3.17 | 3.37 / 4.36 / 3.89; 2048 x 128: 1.12 / 1.34 /
# 1.16 | 0.77 / 0.95 / 0.78; 4096 x 128: 1.86 / 2.28 / 2.01 | 1.41 / 1.74 /
# 1.45.  The feature-major kernels at the same sizes (PR 37, the same calls,
# PR 31's kernels beside them in one chip run): 1024 x 64 0.78 / 0.97 / 0.81
# -> 0.74 / 0.79 / 0.69; 2048 x 64 1.32 / 1.93 / 1.66 -> 1.33 / 1.53 / 1.25;
# 4096 x 64 2.43 / 3.51 / 3.05 -> 2.43 / 2.80 / 2.37; 2048 x 128 0.77 / 0.95 /
# 0.78 -> 0.76 / 1.01 / 0.80; 4096 x 128 1.36 / 1.74 / 1.46 -> 1.42 / 1.83 /
# 1.46 (at 128 features nothing was padded before either, and delta is now
# the kernels' own work: +3 % in the kernels, -0.2 ms a call of XLA ops
# around them).
PREFERRED_SUB = 256
# Columns (rows of the sequence) of a block at 2-byte operands of up to 128
# features; wider elements or heads take fewer (f32 in the parity tests:
# 512), narrower ones no more: most of what fills VMEM is float32 whatever
# the operands.  First to fill it is ``bwd_dkv``: q, k, v, O, dO and the
# result as ``[head_dim, columns]`` blocks, unpadded (128 B a column each at
# 64 features of bf16; 256 at 128), lse as a ``[1, columns]`` f32 row (32 B a
# column in VMEM's eight sublanes, where a ``[columns, 1]`` block took
# 512), all double-buffered, two ``[head_dim, columns]`` f32 accumulators and
# four f32 tiles of ``sub`` x columns — about 7 KB a column at 128 features,
# of the 16 MB a kernel may use by default.  (Under a 64 MB limit 2048-row
# blocks ran S = 2048 and 4096 another 12–22 % faster on PR 31's kernels:
# ROADMAP S2, not shipped.)
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
        if block_q != S and block_q % 128:
            # the sequence lies along the lanes: a block is whole 128-lane
            # columns, or the sequence
            raise ValueError(
                f"sequence {S} divides into no blocks of whole 128-lane "
                f"columns")
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
    key sub-block ``j`` meets the query sub-blocks ``first .. n-1`` in one
    tile, and on a pair the ``diagonal`` runs through, the first of them is
    the square it crosses (the only one that is masked: a key meets the
    queries from its own on); a pair wholly under the diagonal, and every
    pair of a call that is not causal, meets everything unmasked.  All
    three kernels walk the keys."""
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


def _scores(q, k, *, scale, masked):
    """Scaled scores s = kᵀq·scale in f32 as ``[keys, queries]``, from
    ``k [D, keys]`` and ``q [D, queries]``; ``masked``: the tile's square on
    the diagonal (all its keys against its first queries: both sides start
    at the same row of the sequence) keeps what is on or under it — shared
    by fwd and both bwd kernels so the mask/scale math cannot diverge."""
    s = _dot(k, q, 0, 0) * scale
    if masked:
        side = k.shape[1]
        square, rest = s[:, :side], s[:, side:]
        query = jax.lax.broadcasted_iota(jnp.int32, square.shape, 1)
        key = jax.lax.broadcasted_iota(jnp.int32, square.shape, 0)
        square = jnp.where(query >= key, square, NEG_INF)
        s = (jnp.concatenate([square, rest], axis=1) if rest.shape[1]
             else square)
    return s


def _p_ds(q, k, v, do, lse, delta, *, scale, masked):
    """Recompute (p, ds) for the backward kernels, ``[keys, queries]``:
    p = exp(s − lse), ds = p ∘ (vᵀdO − delta)·scale, with lse and delta as
    ``[1, queries]`` rows."""
    s = _scores(q, k, scale=scale, masked=masked)
    p = jnp.exp(s - lse)
    ds = p * (_dot(v, do, 0, 0) - delta) * scale
    return p, ds


def _delta(delta_ref, o_ref, do_ref):
    """delta = Σ_d dO∘O of a block of queries as the ``[1, queries]`` row
    (VMEM scratch: the walk reads slices of it): a multiply and a sum down
    the ``head_dim`` sublanes, in f32."""
    delta_ref[...] = jnp.sum(do_ref[0].astype(jnp.float32)
                             * o_ref[0].astype(jnp.float32), axis=0,
                             keepdims=True)


def _put(out_ref, acc_ref, cols, value):
    """A piece of a backward result: straight out where the step sees the
    whole sequence (there is no accumulator), else added up over the steps."""
    if acc_ref is None:
        out_ref[0, :, cols] = value.astype(out_ref.dtype)
    else:
        acc_ref[:, cols] += value


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, plan):
    iq, ik = pl.program_id(2), pl.program_id(3)
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
            k, v = k_ref[0, :, keys], v_ref[0, :, keys]          # [D, SUB]
            s = _scores(q_ref[0, :, queries], k, scale=scale,
                        masked=diagonal)                         # [SUB, Q]
            m_prev = m_ref[:, queries]                           # [1, Q]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)                               # [SUB, Q]
            corr = jnp.exp(m_prev - m_new)
            l_ref[:, queries] = (l_ref[:, queries] * corr
                                 + jnp.sum(p, axis=0, keepdims=True))
            acc_ref[:, queries] = (acc_ref[:, queries] * corr
                                   + _dot(v, p.astype(v.dtype), 1, 0))
            m_ref[:, queries] = m_new

    _for_pair(iq, ik, plan, causal, False, walk)

    @pl.when(ik == plan.n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)        # [D, BQ]
        lse_ref[0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))  # [1, BQ]


O, LSE = "o", "lse"          # what a spec is of, beside q, k, v (0, 1, 2)


def _specs(plan, D, H, causal, fused, keys_own=False):
    """``(own, other)``: makers of the BlockSpecs of the block a grid step
    ``(b, n, i, im)`` owns and of the one it is handed block by block.
    ``own(part)`` / ``other(part)`` is a head's ``[D, block_q]`` block of a
    ``[B, F, S]`` array: with ``part`` 0, 1, 2 of q, k, v — in the fused
    projection's (``fused``) the row blocks ``3n + part`` of one array,
    else block ``n`` of their own — with :data:`O` block ``n`` of an array
    of the heads alone (O, dO, a gradient of three), and with :data:`LSE`
    the head's ``[1, block_q]`` row of the ``[B·H, 1, S]`` array.  A pair
    above the diagonal asks for the nearest live pair's block — the one
    already in VMEM, so a dead step copies nothing."""
    def spec(part, column):
        def index(b, n, i, im):
            if part == LSE:
                return (b * H + n, 0, column(i, im))
            return (b, 3 * n + part if fused and part != O else n,
                    column(i, im))
        return pl.BlockSpec((1, 1 if part == LSE else D, plan.block_q),
                            index)

    def handed(i, im):
        if causal:
            im = jnp.maximum(im, i) if keys_own else jnp.minimum(im, i)
        return im

    return (lambda part: spec(part, lambda i, im: i),
            lambda part: spec(part, handed))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _fwd(x, *, head_dim, scale, causal, plan, interpret):
    """``x``: the fused projection ``(qkv,)`` as ``[B, 3·H·D, S]`` or
    ``(q, k, v)`` as ``[B, H·D, S]`` each → ``o [B, H·D, S]`` and the
    log-sum-exp rows ``[B·H, 1, S]`` f32 (the residual, as the backward
    reads it)."""
    fused, D = len(x) == 1, head_dim
    B, F, S = x[0].shape
    H = F // (3 * D if fused else D)
    own, other = _specs(plan, D, H, causal, fused)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, plan=plan),
        grid=(B, H, plan.n_blocks, plan.n_blocks),
        in_specs=[own(0), other(1), other(2)],
        out_specs=[own(O), own(LSE)],
        scratch_shapes=[
            pltpu.VMEM((D, plan.block_q), jnp.float32),
            pltpu.VMEM((1, plan.block_q), jnp.float32),
            pltpu.VMEM((1, plan.block_q), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H * D, S), x[0].dtype),
            jax.ShapeDtypeStruct((B * H, 1, S), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=FWD_NAME,
    )(*(x * 3 if fused else x))


# -- backward --------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                    fused, scale, causal, plan):
    ik, iq = pl.program_id(2), pl.program_id(3)
    sub, D = plan.sub, k_ref.shape[1]
    if fused:
        # one block of the projection's gradient: the head's dq rows are
        # bwd_dq's to write, after this call and into the same array
        dqkv_ref, delta_ref, *acc = rest
        dk_ref = dqkv_ref.at[:, pl.ds(D, D), :]
        dv_ref = dqkv_ref.at[:, pl.ds(2 * D, D), :]
    else:
        dk_ref, dv_ref, delta_ref, *acc = rest
    dk_acc, dv_acc = acc or (None, None)

    if acc:
        @pl.when(iq == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    def walk(diagonal):
        _delta(delta_ref, o_ref, do_ref)
        for j, first in _walk(plan.n_sub, diagonal):
            keys = slice(j * sub, (j + 1) * sub)
            queries = slice(first * sub, None)
            q, do = q_ref[0, :, queries], do_ref[0, :, queries]  # [D, Q]
            p, ds = _p_ds(q, k_ref[0, :, keys], v_ref[0, :, keys], do,
                          lse_ref[0, :, queries], delta_ref[:, queries],
                          scale=scale, masked=diagonal)          # [SUB, Q]
            # dv += dO pᵀ ; dk += q dsᵀ                          # [D, SUB]
            _put(dv_ref, dv_acc, keys, _dot(do, p.astype(do.dtype), 1, 1))
            _put(dk_ref, dk_acc, keys, _dot(q, ds.astype(q.dtype), 1, 1))

    _for_pair(ik, iq, plan, causal, True, walk)

    if acc:
        @pl.when(iq == plan.n_blocks - 1)
        def _finalize():
            dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, *rest,
                   fused, scale, causal, plan):
    iq, ik = pl.program_id(2), pl.program_id(3)
    sub = plan.sub
    # fused: rest opens with bwd_dkv's array, which the result aliases
    dq_ref, delta_ref, dq_acc = rest[1:] if fused else rest
    whole = plan.n_blocks == 1

    if not whole:
        @pl.when(ik == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    def walk(diagonal):
        _delta(delta_ref, o_ref, do_ref)
        for j, first in _walk(plan.n_sub, diagonal):
            keys = slice(j * sub, (j + 1) * sub)
            queries = slice(first * sub, None)
            k = k_ref[0, :, keys]                                # [D, SUB]
            _, ds = _p_ds(q_ref[0, :, queries], k, v_ref[0, :, keys],
                          do_ref[0, :, queries], lse_ref[0, :, queries],
                          delta_ref[:, queries], scale=scale,
                          masked=diagonal)                       # [SUB, Q]
            dq = _dot(k, ds.astype(k.dtype), 1, 0)               # [D, Q]
            if whole and j == 0:     # the first piece meets every query
                dq_acc[...] = dq
            else:
                dq_acc[:, queries] += dq

    _for_pair(iq, ik, plan, causal, False, walk)

    @pl.when(ik == plan.n_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd(x, o, lse, do, *, head_dim, scale, causal, plan, interpret):
    """The gradient of ``x`` in ``x``'s own form: ``(dqkv,)`` as one
    ``[B, 3·H·D, S]`` array that ``bwd_dkv`` makes and ``bwd_dq`` completes
    in place, or ``(dq, dk, dv)``."""
    fused, D = len(x) == 1, head_dim
    B, F, S = x[0].shape
    H = F // (3 * D if fused else D)
    grid = (B, H, plan.n_blocks, plan.n_blocks)
    operands = (*(x * 3 if fused else x), o, do, lse)
    like = jax.ShapeDtypeStruct(x[0].shape, x[0].dtype)
    acc = pltpu.VMEM((D, plan.block_q), jnp.float32)
    row = pltpu.VMEM((1, plan.block_q), jnp.float32)            # delta

    def call(kernel, name, in_specs, out_specs, out_shape, scratch, *more,
             **kw):
        return pl.pallas_call(
            functools.partial(kernel, fused=fused, scale=scale,
                              causal=causal, plan=plan),
            grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=_PARAMS, interpret=interpret, name=name, **kw,
        )(*operands, *more)

    # bwd_dkv owns a key block; q, O, dO and lse come block by block.  A
    # step that sees the whole sequence writes its results straight out,
    # else f32 accumulators add them up over the grid's last axis
    own, other = _specs(plan, D, H, causal, fused, keys_own=True)
    in_specs = [other(0), own(1), own(2), other(O), other(O), other(LSE)]
    scratch = [row] if plan.n_blocks == 1 else [row, acc, acc]
    if fused:
        whole_head = pl.BlockSpec((1, 3 * D, plan.block_q),
                                  lambda b, n, i, im: (b, n, i))
        dkv = call(_bwd_dkv_kernel, BWD_DKV_NAME, in_specs, whole_head, like,
                   scratch)
    else:
        dk, dv = call(_bwd_dkv_kernel, BWD_DKV_NAME, in_specs,
                      [own(1), own(2)], [like, like], scratch)

    # bwd_dq owns a query block; k and v come block by block
    own, other = _specs(plan, D, H, causal, fused)
    in_specs = [own(0), other(1), other(2), own(O), own(O), own(LSE)]
    if fused:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        return (call(_bwd_dq_kernel, BWD_DQ_NAME, in_specs, own(0), like,
                     [row, acc], dkv, input_output_aliases={len(operands): 0}),)
    dq = call(_bwd_dq_kernel, BWD_DQ_NAME, in_specs, own(0), like,
              [row, acc])
    return dq, dk, dv


# -- public entries (custom_vjp over the kernels' own [B, F, S]) -----------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash(x, head_dim, causal, plan, interpret):
    o, _ = _fwd(x, head_dim=head_dim, scale=1.0 / math.sqrt(head_dim),
                causal=causal, plan=plan, interpret=interpret)
    return o


def _flash_fwd(x, head_dim, causal, plan, interpret):
    o, lse = _fwd(x, head_dim=head_dim, scale=1.0 / math.sqrt(head_dim),
                  causal=causal, plan=plan, interpret=interpret)
    return o, (x, o, lse)


def _flash_bwd(head_dim, causal, plan, interpret, res, g):
    return (_bwd(*res, g, head_dim=head_dim, scale=1.0 / math.sqrt(head_dim),
                 causal=causal, plan=plan, interpret=interpret),)


_flash.defvjp(_flash_fwd, _flash_bwd)
# jitted so that the unrolled walks are traced once a shape and not once a
# layer and pass of the program that calls them (PR 27's lesson: 24 layers x
# the build's passes)
_flash_traced_once = jax.jit(_flash, static_argnums=(1, 2, 3, 4))


def _planned(S, D, dtype, causal, block_q, sub, fused):
    plan = flash_plan(S, D, dtype.itemsize, causal, block_q, sub)
    # the engagement share of the causal skip and the operands' form, once
    # a traced call
    _spans.mark("attention.flash_plan", seq=S, head_dim=D,
                causal=int(causal), block_q=plan.block_q, sub=plan.sub,
                tiles_total=plan.tiles_total,
                tiles_visited=plan.tiles_visited,
                tiles_masked=plan.tiles_masked,
                layout="feature_major", fused_qkv=int(fused))
    return plan


def flash_attention_fused(q, k, v, causal=True, block_q=None, block_k=None,
                          interpret=False):
    """q/k/v: [B, S, H, D] → [B, S, H, D].  ``block_q`` / ``block_k`` (the
    walk's sub-block) pin a size of :func:`flash_plan`.  The kernels get
    each tensor as ``[B, H·D, S]``; the transposes are logical, and XLA's
    layout assignment carries them to whatever produces the tensor."""
    B, S, H, D = q.shape
    if k.shape[1] != S:
        raise ValueError(
            f"flash_attention_fused requires Sq == Sk (self-attention); got "
            f"q seq {S}, k seq {k.shape[1]} — use the XLA oracle for "
            f"cross-attention/decode")
    plan = _planned(S, D, q.dtype, causal, block_q, block_k, False)

    def feature_major(x):
        return x.transpose(0, 2, 3, 1).reshape(B, H * D, S)

    o = _flash_traced_once((feature_major(q), feature_major(k),
                            feature_major(v)), D, causal, plan, interpret)
    return o.reshape(B, H, D, S).transpose(0, 3, 1, 2)


def flash_attention_fused_qkv(qkv, causal=True, block_q=None, block_k=None,
                        interpret=False):
    """qkv: a head-major fused projection ``[B, S, H, 3·D]`` (a head's q, k
    and v side by side) → ``[B, S, H, D]``.  The kernels read the three out
    of the one array, as ``[B, 3·H·D, S]`` — the order in which XLA:TPU
    lays the projection's output out anyway — and write the gradient back
    into one such array; nothing is split or joined."""
    B, S, H, D3 = qkv.shape
    D = D3 // 3
    plan = _planned(S, D, qkv.dtype, causal, block_q, block_k, True)
    o = _flash_traced_once((qkv.reshape(B, S, H * D3).transpose(0, 2, 1),),
                           D, causal, plan, interpret)
    return o.transpose(0, 2, 1).reshape(B, S, H, D)


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

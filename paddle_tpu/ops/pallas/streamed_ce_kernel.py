"""The streamed cross-entropy's forward statistics as one Pallas TPU kernel.

``ops/fused.py`` needs, per row of ``h [N, H]`` against the vocab-major head
weight ``w [V, H]``, the log-sum-exp of the row's logits over the vocabulary
and the label's logit.  The scan it runs elsewhere writes each block of
float32 logits to HBM and reads it back twice (running max and pick, sum of
exponentials): at the train cell (rows 16,384, hidden 1,024, vocabulary
50,304, bf16) two passes over 134 MB a block cost more than the block's
matmul.  Here a tile of logits goes from the MXU's float32 accumulator to
the running ``(max, sum-exp, picked)`` and never leaves VMEM.

The tile is held transposed, ``[vocabulary, rows]`` (``w_tile @ h_tile^T``,
both operands contracted over their last axis as they are stored): the
vocabulary lies on sublanes, so a reduction over it is elementwise across
vector registers with one sublane fold at the end, and every statistic is a
lane-dense ``[1, rows]`` row — labels in, ``lse`` and ``picked`` out, as
``[1, N]`` arrays with no padded column.

The mathematics and the precisions are the scan's: operands in the compute
dtype, products accumulated in float32, every statistic float32; only the
order of the float32 sums differs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import _precision_for

F32 = jnp.float32

#: the custom call's name, and with it its event's in a profiler trace; in
#: an op_name it is one more part under the scope ``ops/fused.py`` traces the
#: loss under (``loss.streamed_ce/streamed_ce_fwd``), as every kernel's is
FWD_NAME = "streamed_ce_fwd"

# Tuned on v5e at the train cell's call, the kernel alone (ms a call; the
# scan it replaces 23.06, the matmul alone with a bf16 result 9.62): rows x
# vocabulary of a grid step 2048 x 1024 as one matmul 10.91; its vocabulary
# in unrolled pieces of 512: 10.56 (rows in pieces of 512), 256: **10.20**
# (10.18 / 10.51 / 10.32 with the rows in pieces of 1024 / 512 / 256 too),
# 128: 10.47; the same pieces in a ``fori_loop`` 11.5–13.2 (the MXU of one
# piece and the VPU of the last overlap only inside a basic block); 2048 x
# 512: 10.34, 1024 x 1024: 10.54, 4096 x 512 whole: 10.66; 2048 x 2048 and
# 4096 x 1024: 15.2–15.7.  Rows on sublanes (``[rows, vocabulary]`` tiles,
# ``[rows, 1]`` statistics): 11.35 whole, 12.0–15.6 in pieces.  Leaving the
# pick out changes nothing (10.51 | 10.81): the kernel is the MXU's.
ROW_TILE = 2048      # rows whose hidden states stay resident a row of the grid
VOCAB_TILE = 1024    # rows of the head weight a grid step streams through
VOCAB_SUB = 256      # of them, a matmul of the body's unrolled walk
#: what the resident ``[tm, H]`` tile may take in VMEM (twice: two buffers);
#: wider hidden states than 2,048 bf16 lanes get fewer rows, and the weight's
#: tile half of that
ROW_TILE_BYTES = 8 << 20
#: the kernel's scoped VMEM: the two tiles twice (12 MiB at the train cell,
#: 24 at the most) and a piece's float32 logits with their temporaries, over
#: the compiler's default 16 of a v5e's 128
VMEM_BYTES = 64 << 20


def supports(rows: int, hidden: int, vocab: int, dtype) -> bool:
    """Whether the kernel takes the call as Mosaic wants it: whole lanes of
    rows and of the contraction, a vocabulary of one lane tile or more."""
    return (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32))
            and rows % 128 == 0 and hidden % 128 == 0 and vocab >= 128)


def plan(rows: int, hidden: int, vocab: int, itemsize: int):
    """``(tm, tn, sub)``: the rows and the vocabulary of a grid step and the
    vocabulary of one matmul in it, from the operands' shapes."""
    fit = max(128, ROW_TILE_BYTES // (hidden * itemsize) // 128 * 128)
    tm = min(ROW_TILE, fit, rows)
    # whole lane tiles, no more than the vocabulary holds (all of one smaller)
    tn = min(VOCAB_TILE, max(128, fit // 2), vocab // 128 * 128 or vocab)
    sub = next((s for s in (VOCAB_SUB, 128) if tn % s == 0), tn)
    return tm, tn, sub


def _stats_kernel(lbl_ref, h_ref, w_ref, lse_ref, picked_ref, m_ref, s_ref,
                  p_ref, *, vocab, sub):
    j, nj = pl.program_id(1), pl.num_programs(1)
    tn = w_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        s_ref[...] = jnp.zeros_like(s_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    def walk(ragged):
        h = h_ref[...]
        lbl = lbl_ref[...] - j * tn          # the label's row of this tile
        m, s, p = m_ref[...], s_ref[...], p_ref[...]
        for v0 in range(0, tn, sub):
            # [sub, tm] logits in f32 straight off the MXU accumulator
            logits = jax.lax.dot_general(
                w_ref[v0:v0 + sub, :], h, (((1,), (1,)), ((), ())),
                preferred_element_type=F32,
                precision=_precision_for(h.dtype))
            row = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0) + v0
            if ragged:
                # past the vocabulary's end the block holds what it holds
                logits = jnp.where(row < vocab - j * tn, logits, -jnp.inf)
            new_m = jnp.maximum(m, jnp.max(logits, axis=0, keepdims=True))
            s = s * jnp.exp(m - new_m) + jnp.sum(
                jnp.exp(logits - new_m), axis=0, keepdims=True)
            p = p + jnp.sum(jnp.where(row == lbl, logits, 0.0), axis=0,
                            keepdims=True)
            m = new_m
        m_ref[...], s_ref[...], p_ref[...] = m, s, p

    if vocab % tn:
        pl.when(j == nj - 1)(functools.partial(walk, True))
        pl.when(j < nj - 1)(functools.partial(walk, False))
    else:
        walk(False)

    @pl.when(j == nj - 1)
    def _out():
        lse_ref[...] = m_ref[...] + jnp.log(s_ref[...])
        picked_ref[...] = p_ref[...]


def streamed_ce_stats(h, w, labels, interpret=False):
    """``h [N, H]`` and ``w [V, H]`` in the compute dtype, ``labels [N]``
    int32 in ``[0, V)`` → ``(lse, picked)``, float32 ``[N]`` each: the
    log-sum-exp of row ``n``'s logits ``h[n] @ w.T`` and the logit of
    ``labels[n]``.  Rows past a ragged last row tile and vocabulary past a
    ragged last block are the grid's own padding: the first are dropped on
    the way out, the second masked to ``-inf`` by index."""
    N, H = h.shape
    V = w.shape[0]
    tm, tn, sub = plan(N, H, V, h.dtype.itemsize)
    stat = pl.BlockSpec((1, tm), lambda i, j: (0, i))
    lse, picked = pl.pallas_call(
        functools.partial(_stats_kernel, vocab=V, sub=sub),
        grid=(pl.cdiv(N, tm), pl.cdiv(V, tn)),
        in_specs=[stat, pl.BlockSpec((tm, H), lambda i, j: (i, 0)),
                  pl.BlockSpec((tn, H), lambda i, j: (j, 0))],
        out_specs=[stat, stat],
        out_shape=[jax.ShapeDtypeStruct((1, N), F32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, tm), F32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name=FWD_NAME,
    )(labels.reshape(1, N), h, w)
    return lse.reshape(N), picked.reshape(N)

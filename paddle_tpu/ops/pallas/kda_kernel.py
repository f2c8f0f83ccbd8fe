"""Pallas TPU kernels of the gated delta rule with a per-channel forget gate
(Kimi Delta Attention): the chunked scan of a tail prefill and the one-token
step of a decode batch.

The recurrence, a head, on ``S [d_k, d_v]`` (float32; ``g <= 0`` the log of
the per-channel decay, ``beta`` in (0, 1) the write strength)::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`kda_recurrence` is that, token by token (the tests' oracle; nothing
served calls it).

**The chunked form** (:func:`kda_chunk_prefill`).  Inside a chunk of ``C``
rows with ``G_i`` the sum of ``g`` over the chunk's rows up to and including
``i``, ``u_i = beta_i (v_i - S'_i^T k_i)`` solves the unit lower-triangular
system ``(I + A) U = beta V - (beta K * exp(G)) S_0`` with ``A[i, j] =
sum_d beta_i k_i[d] k_j[d] exp(G_i[d] - G_j[d])`` for ``j < i``; then ``O =
(Q * exp(G)) S_0 + B U`` with ``B[i, j]`` the same sum over ``q_i`` for ``j
<= i``, and ``S_C = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U``.  Every
exponent that is taken is ``<= 0``: a strong gate (``g`` of -20 a token)
underflows to an exact zero where a factor taken against the chunk's start
(``exp(-G_j)``) would overflow.  To that end the scores are built by
sub-chunks of :data:`SUB` rows: a block below the diagonal through the
reference point between the two (``exp(G_i - G_ref) * exp(G_ref - G_j)``, a
matmul), a block on the diagonal pair by pair (``exp(G_i - G_j)`` itself).
The system is solved by forward substitution, a rank-one update a row.

Heads are the outer, parallel grid axis, a head's chunks the inner,
sequential one; ``S`` stays in VMEM scratch from a head's first chunk to its
last.  Rows past the real end are given ``g = 0`` and ``beta = 0`` outside
the kernel and so reach no state: the state after the bucket's last row is
the state at the real end.  The state at up to ``K`` further tail-relative
ends (a snapshot each) is taken inside the chunk an end falls in.

**The step** (:func:`kda_decode_step`) reads and writes the state of the
running slots only: the list of running slots is a scalar-prefetch operand,
the grid's bound is their number, and the state buffer is aliased to the
result, so a slot that does not run keeps its bytes.  It is bound by bytes:
a slot's state once in, once out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a chunk of the scan, and of a sub-chunk of its score blocks
CHUNK = 64
SUB = 16
#: heads a grid step of the decode kernel takes (a ``[16, 128, 128]`` float32
#: block is 1 MiB; in and out, double-buffered, 4 MiB)
STEP_HEADS = 16
#: named scopes of the operator in a compiled program's op names: the
#: chunked scan, the decode step, and the rest of the operator (the short
#: convolution, the gates, the norms)
SCAN_SCOPE = "kda.scan"
STEP_SCOPE = "kda.step"
MIX_SCOPE = "kda.mix"

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta, s0):
    """Token by token: ``q``/``k``/``v``/``g [S, H, D]``, ``beta [S, H]``,
    ``s0 [H, D, D]``; returns ``(o [S, H, D], states [S, H, D, D])``, the
    state after every token; float32."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        pred = jnp.einsum("hkv,hk->hv", s, kt, precision=_HI)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        return s, (jnp.einsum("hkv,hk->hv", s, qt, precision=_HI), s)

    xs = tuple(a.astype(F32) for a in (q, k, v, g, beta))
    _, (o, states) = jax.lax.scan(step, s0.astype(F32), xs)
    return o, states


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=F32)


def _column(row, n: int):
    """``row [1, n]`` as ``[n, 1]`` by a select and a lane reduction (no
    transpose of a one-row tile)."""
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _scan_kernel(ends_ref, q_ref, k_ref, kb_ref, vb_ref, gc_ref, s0_ref,
                 o_ref, s_ref, snap_ref, state, *, C, sub, K):
    c = pl.program_id(1)
    D = q_ref.shape[-1]
    nb = C // sub

    @pl.when(c == 0)
    def _():
        state[...] = s0_ref[0]
        snap_ref[...] = jnp.zeros_like(snap_ref)

    q, k, kb, vb, gc = (r[0] for r in (q_ref, k_ref, kb_ref, vb_ref, gc_ref))
    S = state[...]
    eg = jnp.exp(gc)
    rhs = vb - _dot(kb * eg, S)                     # [C, d_v]
    o = _dot(q * eg, S)
    # the blocks below the diagonal, a row of blocks at a time: through the
    # cumulative gate of the row before the block's first
    nt = (((1,), (1,)), ((), ()))
    row = jax.lax.broadcasted_iota(jnp.int32, (C, D), 0)
    A_rows, B_rows = [jnp.zeros((sub, C), F32)], [jnp.zeros((sub, C), F32)]
    for a in range(1, nb):
        lo = a * sub
        ref = gc[lo - 1:lo]                         # [1, D]
        left = jnp.exp(gc[lo:lo + sub] - ref)       # [sub, D], <= 1
        right = jnp.where(row < lo,
                          k * jnp.exp(jnp.minimum(ref - gc, 0.0)), 0.0)
        A_rows.append(_dot(kb[lo:lo + sub] * left, right, nt))
        B_rows.append(_dot(q[lo:lo + sub] * left, right, nt))
    # the blocks on the diagonal, pair by pair: exp(G_i - G_j) itself
    gb, kk, kbb, qb = (x.reshape(nb, sub, D) for x in (gc, k, kb, q))
    col_of = jax.lax.broadcasted_iota(jnp.int32, (nb, sub, C), 2) \
        - sub * jax.lax.broadcasted_iota(jnp.int32, (nb, sub, C), 0)
    Ad = jnp.zeros((nb, sub, C), F32)
    Bd = jnp.zeros((nb, sub, C), F32)
    for j in range(sub):
        e = kk[:, j:j + 1] * jnp.exp(jnp.minimum(gb - gb[:, j:j + 1], 0.0))
        Ad = jnp.where(col_of == j,
                       jnp.sum(kbb * e, axis=-1, keepdims=True), Ad)
        Bd = jnp.where(col_of == j,
                       jnp.sum(qb * e, axis=-1, keepdims=True), Bd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    A = jnp.concatenate(A_rows, axis=0) \
        + jnp.where(cols < rows, Ad.reshape(C, C), 0.0)
    B = jnp.concatenate(B_rows, axis=0) \
        + jnp.where(cols <= rows, Bd.reshape(C, C), 0.0)
    # (I + A) U = rhs, forward: once row l is final, every later row takes
    # its share of it
    U = rhs
    for l in range(C - 1):
        U = U - A[:, l:l + 1] * U[l:l + 1]
    o_ref[0] = (o + _dot(B, U)).astype(o_ref.dtype)
    tn = (((0,), (0,)), ((), ()))
    for i in range(K):
        # the state after tail row ``ends[i] - 1``, where it is this chunk's
        r = ends_ref[i] - 1 - c * C

        @pl.when(jnp.logical_and(r >= 0, r < C))
        def _(i=i, r=r):
            at = gc_ref[0, pl.ds(r, 1), :]          # [1, D]
            w = jnp.where(row <= r,
                          jnp.exp(jnp.minimum(at - gc, 0.0)), 0.0)
            snap_ref[i, 0] = _column(jnp.exp(at), D) * S + _dot(k * w, U, tn)

    last = gc[C - 1:C]
    S = _column(jnp.exp(last), D) * S + _dot(k * jnp.exp(last - gc), U, tn)
    state[...] = S

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_ref[0] = S


@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def kda_chunk_prefill(q, k, v, g, beta, s0, ends, n, *, interpret=False,
                      chunk=CHUNK):
    """The recurrence over one sequence's rows from ``s0``.

    Args:
        q, k, v: ``[S, H, D]`` (``q`` scaled, ``q`` and ``k`` normalised, as
            the recurrence takes them).
        g:       ``[S, H, D]`` float32, ``<= 0``.
        beta:    ``[S, H]``.
        s0:      ``[H, D, D]`` float32: the state before row 0.
        ends:    ``[K]`` int32: row counts to return the state after (0, or
            past ``n``: zeros come back).
        n:       int32 scalar: the real rows; the rest reach no state.

    Returns:
        ``(o [S, H, D], s_n [H, D, D], snaps [K, H, D, D])``, float32.
    """
    S, H, D = q.shape
    C, K = chunk, ends.shape[0]
    sub = min(SUB, C)
    pad = -S % C
    n = jnp.asarray(n, jnp.int32).reshape(())
    with jax.named_scope(MIX_SCOPE):
        live = (jnp.arange(S + pad, dtype=jnp.int32) < n)[None, :, None]

        def heads_first(x, mask=False):
            x = jnp.pad(x.astype(F32), ((0, pad), (0, 0), (0, 0)))
            x = jnp.transpose(x, (1, 0, 2))                   # [H, S, D]
            return jnp.where(live, x, 0.0) if mask else x

        b = beta.astype(F32)[:, :, None]
        qh, kh = heads_first(q, True), heads_first(k, True)
        kb, vb = heads_first(k.astype(F32) * b, True), \
            heads_first(v.astype(F32) * b, True)
        gh = heads_first(g, True)
        gc = jnp.cumsum(gh.reshape(H, -1, C, D), axis=2).reshape(gh.shape)
        ends = jnp.where(ends <= n, ends, 0).astype(jnp.int32)
    n_chunks = (S + pad) // C
    row = pl.BlockSpec((1, C, D), lambda h, c, e: (h, c, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, n_chunks),
        in_specs=[row] * 5 + [
            pl.BlockSpec((1, D, D), lambda h, c, e: (h, 0, 0))],
        out_specs=[
            row,
            pl.BlockSpec((1, D, D), lambda h, c, e: (h, 0, 0)),
            pl.BlockSpec((K, 1, D, D), lambda h, c, e: (0, h, 0, 0))],
        scratch_shapes=[pltpu.VMEM((D, D), F32)],
    )
    with jax.named_scope(SCAN_SCOPE):
        o, s_n, snaps = pl.pallas_call(
            functools.partial(_scan_kernel, C=C, sub=sub, K=K),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((H, S + pad, D), F32),
                       jax.ShapeDtypeStruct((H, D, D), F32),
                       jax.ShapeDtypeStruct((K, H, D, D), F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="kda_chunk_prefill",
        )(ends, qh, kh, kb, vb, gc, s0.astype(F32))
    with jax.named_scope(MIX_SCOPE):
        return jnp.transpose(o, (1, 0, 2))[:S], s_n, snaps


def _step_kernel(slots_ref, n_ref, x_ref, s_in_ref, o_ref, s_out_ref, *, Hb):
    i = pl.program_id(1)
    D = s_in_ref.shape[-1]

    @pl.when(i < n_ref[0])
    def _():
        for h in range(Hb):
            x = x_ref[0, h]                          # [8, D]: q k v a b
            S = s_in_ref[0, h] * _column(x[3:4], D)
            kc = _column(x[1:2], D)
            d = x[4:5] * (x[2:3] - jnp.sum(S * kc, axis=0, keepdims=True))
            S = S + kc * d
            s_out_ref[0, h] = S
            o_ref[0, h] = jnp.broadcast_to(
                jnp.sum(S * _column(x[0:1], D), axis=0, keepdims=True),
                (8, D))

    @pl.when(n_ref[0] == 0)
    def _():                    # no slot runs: the one block visited, as is
        s_out_ref[...] = s_in_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(state, q, k, v, g, beta, active, *, interpret=False):
    """One token for every running slot, in place.

    Args:
        state:  ``[slots, H, D, D]`` float32 (aliased to the result).
        q, k, v, g: ``[slots, H, D]``; ``beta [slots, H]``.
        active: ``[slots]`` int32 mask of the slots that run.

    Returns:
        ``(o [slots, H, D] float32, state)``: rows of idle slots are zero,
        their state is what it was, bit for bit.
    """
    N, H, D, _ = state.shape
    Hb = STEP_HEADS if H % STEP_HEADS == 0 else H
    with jax.named_scope(MIX_SCOPE):
        live = active.astype(jnp.int32) > 0
        n = jnp.sum(live).astype(jnp.int32)
        order = jnp.argsort(jnp.logical_not(live), stable=True
                            ).astype(jnp.int32)
        rows = [q, k, v, jnp.exp(g.astype(F32)),
                jnp.broadcast_to(beta.astype(F32)[:, :, None], (N, H, D))]
        x = jnp.stack([r.astype(F32) for r in rows]
                      + [jnp.zeros((N, H, D), F32)] * 3, axis=2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // Hb, jnp.maximum(n, 1)),
        in_specs=[
            pl.BlockSpec((1, Hb, 8, D), lambda h, i, sl, nn: (sl[i], h, 0, 0)),
            pl.BlockSpec((1, Hb, D, D), lambda h, i, sl, nn: (sl[i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Hb, 8, D), lambda h, i, sl, nn: (sl[i], h, 0, 0)),
            pl.BlockSpec((1, Hb, D, D), lambda h, i, sl, nn: (sl[i], h, 0, 0)),
        ],
    )
    with jax.named_scope(STEP_SCOPE):
        o, state = pl.pallas_call(
            functools.partial(_step_kernel, Hb=Hb),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((N, H, 8, D), F32),
                       jax.ShapeDtypeStruct(state.shape, F32)],
            input_output_aliases={3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="kda_decode_step",
        )(order, n.reshape(1), x, state)
    with jax.named_scope(MIX_SCOPE):
        return jnp.where(live[:, None, None], o[:, :, 0], 0.0), state

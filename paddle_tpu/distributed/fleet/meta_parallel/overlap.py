"""Decomposed-matmul compute/collective overlap for Megatron-TP layers.

T3 (arXiv 2401.16677) observes that the serialized pattern

    GEMM -> all-reduce -> GEMM -> ...

leaves the ICI idle during compute and the MXU idle during the
collective; splitting each tensor-parallel GEMM into ``chunks``
independent sub-GEMMs lets the collective of chunk *c* run while chunk
*c+1*'s dot executes.  GC3 (arXiv 2201.11840) makes the same case for
compiled collective schedules — which is exactly what this module
emits: the chunked forwards below are written inside a **full-manual**
``shard_map`` with hand-placed ``psum`` / ``all_gather`` per chunk, so
XLA's optimized module contains the interleaved

    dot, all-reduce, dot, all-reduce, ...

sequence instead of one fused collective at the layer boundary.  The
property is assertable offline: :func:`paddle_tpu.obs.hlo_cost.
collective_exposure` classifies every collective in the optimized HLO
as overlapped/exposed, and tier-1 pins the exposed count strictly
below the ``chunks=1`` baseline (tests/test_tp_overlap.py).

Decomposition per layer kind:

- **RowParallelLinear** — contraction (K) split: each chunk computes a
  full-size partial product from a K/chunks slice of the (model-sharded)
  input and weight, immediately all-reduced over the model axis; chunk
  c+1's dot overlaps chunk c's reduce.  Partials are reduced in f32:
  XLA:CPU's bf16 AllReducePromotion CHECK-crashes on psum-invariant
  regions (see ``pp_schedule._psum_pipe_f32``), and f32 accumulation is
  the numerically safe choice under AMP anyway.
- **ColumnParallelLinear** — output (N) split: per-chunk local dots;
  with ``gather_output=True`` each chunk's ``all_gather`` is issued as
  soon as its dot retires, overlapping the next chunk's dot.
- **VocabParallelEmbedding** — local-vocab split: per-chunk masked row
  gather + f32 psum.
- **ParallelCrossEntropy** — local-vocab split: one pmax prologue for
  the global max, then per-chunk ``sum(exp)`` + picked-logit partials
  each psummed as produced.

Opt-in and parity contract: layers route through this module only when
their effective ``chunks > 1`` (see :func:`effective_chunks`); at
``chunks<=1`` the layer's original GSPMD path runs untouched, so the
baseline schedule is reproduced *bitwise* (the parity oracle).  The
chunked forwards themselves match the baseline to f32 tolerance (chunk
-order float association).  Preconditions (active mesh with model>1,
shapes divisible by mesh axes and chunks, not inside a manual pipeline
region) fall back to the GSPMD path by returning ``None``.

Backward pass: each chunked forward carries a ``jax.custom_vjp`` whose
backward is the *analytic global-math* gradient (plain jnp ops on
global arrays, partitioned by GSPMD exactly like the ``chunks=1``
backward).  Without this, the generic transpose of a per-chunk ``psum``
emits one all-reduce of the same cotangent per chunk — ``chunks``
copies of an identical collective, back to back, all exposed — and the
overlapped program's exposed count *rises* above the baseline instead
of falling.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....core.dispatch import apply_op
from ... import mesh as mesh_mod
from ...sharding_spec import (
    BATCH_AXES, MODEL_AXIS, SEQ_AXIS, batch_spec, _divisible, _filter_spec,
)

__all__ = [
    "TPOverlapConfig", "apply_tp_overlap", "effective_chunks",
    "set_tp_overlap", "get_tp_overlap",
    "column_parallel_linear", "row_parallel_linear",
    "vocab_parallel_embedding", "parallel_cross_entropy",
]


@dataclass(frozen=True)
class TPOverlapConfig:
    """Chunked-decomposition config: ``chunks`` sub-GEMMs per TP matmul.
    ``chunks=1`` (the default everywhere) is the exact baseline."""

    chunks: int = 4


_active: Optional[TPOverlapConfig] = None


def set_tp_overlap(config: Optional[TPOverlapConfig]):
    """Set (or clear with ``None``) the process-wide default config.
    Per-layer ``overlap_chunks`` settings take precedence."""
    global _active
    _active = config


def get_tp_overlap() -> Optional[TPOverlapConfig]:
    return _active


def effective_chunks(layer_chunks: int) -> int:
    """A layer's effective chunk count: its own setting if >1, else the
    process-wide default, else 1 (baseline path)."""
    if layer_chunks and layer_chunks > 1:
        return int(layer_chunks)
    if _active is not None and _active.chunks > 1:
        return int(_active.chunks)
    return 1


def apply_tp_overlap(layer, config: TPOverlapConfig) -> int:
    """Stamp ``config.chunks`` onto every overlap-capable sublayer of
    ``layer`` (and every sublayer, so models that build their criterion
    lazily — e.g. ``GPTForCausalLM.compute_loss`` — can read the root's
    setting).  Returns the number of capable layers configured."""
    n = 0
    for sub in layer.sublayers(include_self=True):
        sub._tp_overlap_chunks = int(config.chunks)
        if getattr(type(sub), "_tp_overlap_capable", False):
            n += 1
    return n


def _overlap_mesh(chunks: int):
    """The active mesh iff the chunked path can run: chunks>1, a global
    mesh with model-parallel degree >1, and not inside a manual
    (pipeline shard_map) trace region where the global mesh's axis
    types disagree with the trace context."""
    if not chunks or chunks <= 1:
        return None
    m = mesh_mod.get_global_mesh()
    if m is None or m.shape.get(MODEL_AXIS, 1) <= 1:
        return None
    try:
        am = jax.sharding.get_abstract_mesh()
        if am is not None and getattr(am, "shape_tuple", None):
            if any("Manual" in str(t) for t in am.axis_types):
                return None
    except Exception:
        pass
    return m


def _shapes_ok(m, chunks, sharded_dim, *placements):
    """``sharded_dim`` must split over model then chunks; every
    (shape, spec) placement must divide its mesh axes."""
    mp = m.shape[MODEL_AXIS]
    if sharded_dim % mp != 0 or (sharded_dim // mp) % chunks != 0:
        return False
    return all(_divisible(shape, _filter_spec(spec, m), m)
               for shape, spec in placements)


def _smap(m, body, in_specs, out_spec):
    # check_vma=False: the stacked/reshaped all-gather assembly (column
    # path) is not statically inferable as replicated; gradients are
    # exercised by the tier-1 parity suite
    return jax.shard_map(
        body, mesh=m,
        in_specs=tuple(_filter_spec(s, m) for s in in_specs),
        out_specs=_filter_spec(out_spec, m), check_vma=False)


def _linear_vjp(chunked, cdt):
    """Wrap a chunked linear forward ``chunked(x, w, b)`` (``b`` may be
    ``None``) in a custom_vjp whose backward is the analytic global-math
    gradient of ``y = x @ w + b``.  GSPMD partitions these einsums with
    the *same* collective structure as the ``chunks=1`` backward; the
    generic transpose would instead replay one psum per chunk — $chunks$
    identical, serialized, exposed all-reduces of the same cotangent."""

    @jax.custom_vjp
    def f(x_, w_, b_):
        return chunked(x_, w_, b_)

    def fwd(x_, w_, b_):
        return chunked(x_, w_, b_), (x_, w_, b_)

    def bwd(res, g):
        x_, w_, b_ = res
        lead = tuple(range(g.ndim - 1))
        dx = jnp.matmul(g, w_.astype(g.dtype).T).astype(x_.dtype)
        dw = jnp.tensordot(x_.astype(cdt), g,
                           axes=(lead, lead)).astype(w_.dtype)
        db = None if b_ is None else g.sum(axis=lead).astype(b_.dtype)
        return dx, dw, db

    f.defvjp(fwd, bwd)
    return f


def column_parallel_linear(x, weight, bias, chunks: int,
                           gather_output: bool):
    """Chunked ColumnParallelLinear forward, or ``None`` to fall back.

    ``x``: [..., K] replicated over model; ``weight``: [K, N] with N
    model-sharded; output [..., N] (gathered) or [..., N] model-sharded
    (``gather_output=False`` — the Megatron qkv/fc1 case, where the
    chunking keeps the GEMM decomposition uniform with the row layers
    feeding from it)."""
    m = _overlap_mesh(chunks)
    if m is None:
        return None
    k, n = weight.shape
    x_spec = batch_spec(x.ndim, last=None)
    if x.shape[-1] != k or not _shapes_ok(
            m, chunks, n,
            (tuple(x.shape), x_spec),
            (tuple(weight.shape), P(None, MODEL_AXIS))):
        return None
    mp = m.shape[MODEL_AXIS]
    out_spec = batch_spec(x.ndim, last=None if gather_output else MODEL_AXIS)

    def _primal(xa, wa, ba):
        cdt = xa.dtype

        def body(xl, wl, bl=None):
            nl = wl.shape[1]
            ch = nl // chunks
            wl = wl.astype(cdt)
            ys = []
            for c in range(chunks):
                yc = xl @ wl[:, c * ch:(c + 1) * ch]
                if bl is not None:
                    yc = yc + bl[c * ch:(c + 1) * ch].astype(cdt)
                ys.append(yc)
            if not gather_output:
                return jnp.concatenate(ys, axis=-1)
            # chunk c's gather is issued the moment its dot retires,
            # overlapping chunk c+1's dot
            gs = [jax.lax.all_gather(yc, MODEL_AXIS) for yc in ys]
            g = jnp.stack(gs, axis=1)              # [mp, C, ..., ch]
            nd = g.ndim
            g = jnp.transpose(g, tuple(range(2, nd - 1)) + (0, 1, nd - 1))
            return g.reshape(g.shape[:-3] + (mp * chunks * ch,))

        def chunked(x_, w_, b_):
            if b_ is None:
                return _smap(m, body, (x_spec, P(None, MODEL_AXIS)),
                             out_spec)(x_, w_)
            return _smap(m, body,
                         (x_spec, P(None, MODEL_AXIS), P(MODEL_AXIS)),
                         out_spec)(x_, w_, b_)

        return _linear_vjp(chunked, cdt)(xa, wa, ba)

    return apply_op("tp_overlap_column_linear", _primal, [x, weight, bias])


def row_parallel_linear(x, weight, bias, chunks: int):
    """Chunked RowParallelLinear forward, or ``None`` to fall back.

    ``x``: [..., K] model-sharded on K; ``weight``: [K, N] model-sharded
    on K; each K/chunks partial product is psummed (f32) as soon as its
    dot retires — the T3 contraction split."""
    m = _overlap_mesh(chunks)
    if m is None:
        return None
    k, n = weight.shape
    x_spec = batch_spec(x.ndim, last=MODEL_AXIS)
    if x.shape[-1] != k or not _shapes_ok(
            m, chunks, k,
            (tuple(x.shape), x_spec),
            (tuple(weight.shape), P(MODEL_AXIS, None))):
        return None
    out_spec = batch_spec(x.ndim, last=None)

    def _primal(xa, wa, ba):
        cdt = xa.dtype

        def body(xl, wl, bl=None):
            kl = wl.shape[0]
            ch = kl // chunks
            wl = wl.astype(cdt)
            acc = None
            for c in range(chunks):
                part = xl[..., c * ch:(c + 1) * ch] \
                    @ wl[c * ch:(c + 1) * ch, :]
                red = jax.lax.psum(part.astype(jnp.float32), MODEL_AXIS)
                acc = red if acc is None else acc + red
            out = acc.astype(cdt)
            if bl is not None:
                out = out + bl.astype(cdt)
            return out

        def chunked(x_, w_, b_):
            if b_ is None:
                return _smap(m, body, (x_spec, P(MODEL_AXIS, None)),
                             out_spec)(x_, w_)
            return _smap(m, body, (x_spec, P(MODEL_AXIS, None), P()),
                         out_spec)(x_, w_, b_)

        return _linear_vjp(chunked, cdt)(xa, wa, ba)

    return apply_op("tp_overlap_row_linear", _primal, [x, weight, bias])


def vocab_parallel_embedding(x, weight, chunks: int):
    """Chunked VocabParallelEmbedding forward, or ``None`` to fall back:
    per local-vocab chunk, a masked row gather + f32 psum."""
    m = _overlap_mesh(chunks)
    if m is None:
        return None
    v = weight.shape[0]
    x_spec = batch_spec(x.ndim, last=None)
    if not _shapes_ok(m, chunks, v,
                      (tuple(x.shape), x_spec),
                      (tuple(weight.shape), P(MODEL_AXIS, None))):
        return None
    out_spec = batch_spec(x.ndim + 1, last=None)

    def _primal(xa, wa):
        def body(xl, wl):
            vl = wl.shape[0]
            ch = vl // chunks
            base = jax.lax.axis_index(MODEL_AXIS) * vl
            ids = xl.astype(jnp.int32)
            acc = None
            for c in range(chunks):
                rel = ids - (base + c * ch)
                inb = (rel >= 0) & (rel < ch)
                rows = jnp.take(wl[c * ch:(c + 1) * ch],
                                jnp.clip(rel, 0, ch - 1), axis=0)
                rows = jnp.where(inb[..., None],
                                 rows.astype(jnp.float32), 0.0)
                red = jax.lax.psum(rows, MODEL_AXIS)
                acc = red if acc is None else acc + red
            return acc.astype(wa.dtype)

        def chunked(w_):
            return _smap(m, body, (x_spec, P(MODEL_AXIS, None)),
                         out_spec)(xa, w_)

        # ids (xa) are closed over: apply_op never differentiates int
        # args, so the custom_vjp covers the weight only; backward is
        # the plain global scatter-add the chunks=1 path produces
        @jax.custom_vjp
        def f(w_):
            return chunked(w_)

        def fwd(w_):
            return chunked(w_), ()

        def bwd(_, g):
            dw = jnp.zeros(wa.shape, g.dtype).at[xa].add(g)
            return (dw.astype(wa.dtype),)

        f.defvjp(fwd, bwd)
        return f(wa)

    return apply_op("tp_overlap_vocab_embedding", _primal, [x, weight])


def parallel_cross_entropy(logits, label, chunks: int, ignore_index: int):
    """Chunked ParallelCrossEntropy forward, or ``None`` to fall back.

    One pmax prologue establishes the global max; then each local-vocab
    chunk's ``sum(exp)`` and picked-logit partials ride a per-chunk
    psum, interleaving the reductions with the exp fusions."""
    m = _overlap_mesh(chunks)
    if m is None:
        return None
    lg_spec = batch_spec(logits.ndim, last=MODEL_AXIS)
    # labels must split exactly like the logits' batch/seq dims so the
    # per-shard take_along_axis shapes agree inside the body
    lb_ent = [None] * label.ndim
    lb_ent[0] = BATCH_AXES
    if label.ndim >= 2:
        lb_ent[1] = SEQ_AXIS
    lb_spec = P(*lb_ent)
    if not _shapes_ok(m, chunks, logits.shape[-1],
                      (tuple(logits.shape), lg_spec),
                      (tuple(label.shape), lb_spec)):
        return None
    out_spec = batch_spec(logits.ndim, last=None)

    def _primal(lg_a, lb_a):
        def body(lgl, lbl):
            lg = lgl.astype(jnp.float32)
            vl = lg.shape[-1]
            ch = vl // chunks
            base = jax.lax.axis_index(MODEL_AXIS) * vl
            lb_ = lbl[..., None] if lbl.ndim == lg.ndim - 1 else lbl
            mask = lb_ != ignore_index
            ids = lb_.astype(jnp.int32)
            # the lse shift is gradient-free analytically, but pmax has
            # no differentiation rule at all — take the cross-shard max
            # via all_gather (differentiable) on a stopped local max
            lmax = jax.lax.stop_gradient(jnp.max(lg, -1, keepdims=True))
            gmax = jnp.max(jax.lax.all_gather(lmax, MODEL_AXIS), axis=0)
            acc = None
            for c in range(chunks):
                lgc = lg[..., c * ch:(c + 1) * ch]
                s = jnp.sum(jnp.exp(lgc - gmax), -1, keepdims=True)
                rel = ids - (base + c * ch)
                inb = (rel >= 0) & (rel < ch)
                p = jnp.take_along_axis(lgc, jnp.clip(rel, 0, ch - 1),
                                        axis=-1)
                p = jnp.where(inb, p, 0.0)
                red = jax.lax.psum(jnp.concatenate([s, p], -1), MODEL_AXIS)
                acc = red if acc is None else acc + red
            lse = jnp.log(acc[..., :1]) + gmax
            return jnp.where(mask, lse - acc[..., 1:2], 0.0), lse

        def chunked(lg_):
            return jax.shard_map(
                body, mesh=m,
                in_specs=(_filter_spec(lg_spec, m), _filter_spec(lb_spec, m)),
                out_specs=(_filter_spec(out_spec, m),
                           _filter_spec(out_spec, m)),
                check_vma=False)(lg_, lb_a)

        # label is closed over (int, never differentiated); the saved
        # lse makes the backward collective-free: softmax - onehot,
        # elementwise on the vocab-sharded logits
        @jax.custom_vjp
        def f(lg_):
            return chunked(lg_)[0]

        def fwd(lg_):
            loss, lse = chunked(lg_)
            return loss, (lg_, lse)

        def bwd(res, g):
            lg_, lse = res
            lbn = lb_a if lb_a.ndim == lg_.ndim - 1 else lb_a[..., 0]
            mask = (lbn != ignore_index)[..., None]
            sm = jnp.exp(lg_.astype(jnp.float32) - lse)
            oh = (lbn[..., None].astype(jnp.int32)
                  == jnp.arange(lg_.shape[-1], dtype=jnp.int32))
            dlg = jnp.where(mask, g * (sm - oh.astype(jnp.float32)), 0.0)
            return (dlg.astype(lg_.dtype),)

        f.defvjp(fwd, bwd)
        return f(lg_a)

    return apply_op("tp_overlap_cross_entropy", _primal, [logits, label])

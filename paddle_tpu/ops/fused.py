"""Fused ops — the TPU analogs of the reference's hand-written CUDA fusions.

Reference parity targets:
- fused_linear_cross_entropy — the memory fusion of the LM head matmul with
  softmax_with_cross_entropy (reference: the c_softmax_with_cross_entropy op,
  paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu, and
  the fused CE the reference's GPT training applies after the tied-embedding
  projection).  On TPU the bottleneck is HBM, not the kernel launch: a GPT-2
  [B,S,V] logits tensor (B16 S1024 V50304) is 1.6 GB in bf16 and 3.3 GB as
  the f32 softmax temp — it caps the achievable batch and with it MFU.  This
  op never materializes logits: it walks vocab blocks, keeping only f32
  [N]-shaped running (max, sumexp, picked) statistics, and recomputes each
  block's logits in the backward (FLOPs ≈ 4/3 of the unfused head for >10×
  less live memory).  The forward is one Pallas kernel on a TPU
  (``ops/pallas/streamed_ce_kernel.py``: a tile of logits goes from the
  MXU's accumulator to the statistics inside VMEM) and a ``lax.scan`` over
  blocks elsewhere — the kernel's oracle; the backward is a scan everywhere.
  The whole [N, V] logits reach HBM in neither; the scans write a block of
  them at a time (134 MB at the shape above, read back twice by the
  forward's scan: that, not the matmul, was what the kernel took away).
- fused_feedforward / fused_bias_dropout_residual_layer_norm etc. are NOT
  ops here by design: XLA fuses those elementwise chains automatically
  (SURVEY.md §7) — the nn layers compose them and the compiler emits the
  fusion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._helpers import op

__all__ = ["fused_linear_cross_entropy"]

#: the named scope of the streamed fused CE in a compiled program's op names
CE_SCOPE = "loss.streamed_ce"


def _block_view(w, block: int):
    """Pad [V, H] to a multiple of `block` and reshape to [nb, block, H]."""
    V, H = w.shape
    nb = -(-V // block)
    pad = nb * block - V
    if pad:
        w = jnp.pad(w, ((0, pad), (0, 0)))
    return w.reshape(nb, block, H), nb, pad


def _forward_interpret(h2, w, compute_dtype):
    """How the forward computes its statistics: ``None`` by the scan, else
    the kernel's ``interpret`` — ``False`` on a TPU at shapes the kernel
    takes (the repo's rule, ``ops/pallas/__init__.py``; a test that runs the
    kernel on the CPU answers ``True`` here)."""
    from .pallas import use_pallas
    from .pallas.streamed_ce_kernel import supports

    if use_pallas() and supports(*h2.shape, w.shape[0], compute_dtype):
        return False
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flce(h2, w, labels, valid, block, compute_dtype, interpret):
    loss, _ = _flce_fwd(h2, w, labels, valid, block, compute_dtype, interpret)
    return loss


def _scan_stats(hc, wc, lbl, block):
    """``(lse, picked)`` of ``hc [N, H]`` against ``wc [V, H]``, a block of
    the vocabulary a trip: the forward off a TPU, and the kernel's oracle."""
    N = hc.shape[0]
    V = wc.shape[0]
    wb, nb, pad = _block_view(wc, block)
    offsets = jnp.arange(nb, dtype=jnp.int32) * block

    def body(carry, xs):
        m, s, picked = carry
        w_blk, off = xs
        # [N, block] logits in f32 straight off the MXU accumulator
        logits = jax.lax.dot_general(
            hc, w_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = jnp.arange(block, dtype=jnp.int32)[None, :] + off
        logits = jnp.where(col < V, logits, -jnp.inf)
        bm = jnp.max(logits, axis=1)
        new_m = jnp.maximum(m, bm)
        s = s * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(logits - new_m[:, None]), axis=1)
        in_blk = (lbl >= off) & (lbl < off + block)
        idx = jnp.clip(lbl - off, 0, block - 1)
        p = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        picked = jnp.where(in_blk, p, picked)
        return (new_m, s, picked), None

    m0 = jnp.full((N,), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((N,), jnp.float32)
    (m, s, picked), _ = jax.lax.scan(body, (m0, s0, m0), (wb, offsets))
    return m + jnp.log(s), picked


def _flce_fwd(h2, w, labels, valid, block, compute_dtype, interpret):
    """h2 [N,H] activations, w [V,H] vocab-major head weight, labels [N] int,
    valid [N] bool → per-token f32 loss [N] (0 where invalid)."""
    hc = h2.astype(compute_dtype)
    wc = w.astype(compute_dtype)
    lbl = labels.astype(jnp.int32)
    if interpret is None:
        lse, picked = _scan_stats(hc, wc, lbl, block)
    else:
        from .pallas import streamed_ce_stats_on_mesh

        lse, picked = streamed_ce_stats_on_mesh(hc, wc, lbl,
                                                interpret=interpret)
    loss = jnp.where(valid, lse - picked, 0.0)
    return loss, (h2, w, lbl, valid, lse)


def _flce_bwd(block, compute_dtype, interpret, res, g):
    h2, w, lbl, valid, lse = res
    N, H = h2.shape
    V = w.shape[0]
    hc = h2.astype(compute_dtype)
    wb, nb, pad = _block_view(w.astype(compute_dtype), block)
    offsets = jnp.arange(nb, dtype=jnp.int32) * block
    gv = (g * valid).astype(jnp.float32)                  # [N]

    def body(dh, xs):
        w_blk, off = xs
        logits = jax.lax.dot_general(
            hc, w_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = jnp.arange(block, dtype=jnp.int32)[None, :] + off
        p = jnp.where(col < V, jnp.exp(logits - lse[:, None]), 0.0)
        onehot = ((lbl[:, None] - off) == jnp.arange(block, dtype=jnp.int32)
                  [None, :])
        dlogits = (p - onehot) * gv[:, None]              # [N, block] f32
        dlc = dlogits.astype(compute_dtype)
        dh = dh + jax.lax.dot_general(
            dlc, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [N, H]
        dw_blk = jax.lax.dot_general(
            dlc, hc, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [block, H]
        return dh, dw_blk

    dh0 = jnp.zeros((N, H), jnp.float32)
    dh, dw_blocks = jax.lax.scan(body, dh0, (wb, offsets))
    dw = dw_blocks.reshape(nb * block, H)[:V]
    return (dh.astype(h2.dtype), dw.astype(w.dtype), None, None)


_flce.defvjp(_flce_fwd, _flce_bwd)


def fused_linear_cross_entropy(hidden, weight, label, loss_mask=None,
                               ignore_index: int = -100, block_size=None,
                               transpose_weight: bool = False, name=None):
    """Causal-LM loss `cross_entropy(hidden @ weight.T, label)` without ever
    materializing the [..., vocab] logits (see module docstring).

    Args:
        hidden: [..., H] final hidden states (post final-LN).
        weight: [V, H] head weight (the tied-embedding layout); pass
            [H, V] with ``transpose_weight=True`` for nn.Linear weights.
        label: [...] int token ids; ``ignore_index`` positions contribute 0
            loss and 0 gradient.
        loss_mask: optional [...] multiplicative mask.
        block_size: the scans' vocab tile width; None reads
            PADDLE_TPU_FLCE_BLOCK (default 2048) so the bench can sweep
            without code changes.  The TPU forward's kernel sizes its own
            tiles from the operands' shapes.
    Returns:
        scalar mean loss over non-ignored (and mask-weighted) positions.
    """
    if block_size is None:
        import os

        block_size = int(os.environ.get("PADDLE_TPU_FLCE_BLOCK", "2048"))

    def _primal(h, w, lbl, *maybe_mask):
        if transpose_weight:
            w = w.T
        N = 1
        for d in lbl.shape:
            N *= d
        h2 = h.reshape(N, h.shape[-1])
        lblf = lbl.reshape(N).astype(jnp.int32)
        valid = lblf != ignore_index
        # clamp so a stray ignore label can't index out of range
        safe = jnp.clip(lblf, 0, w.shape[0] - 1)
        cdt = h.dtype if h.dtype in (jnp.bfloat16, jnp.float16) else jnp.float32
        # one scope over the streamed loop, forward and backward: autograd
        # wraps it (``jvp(loss.streamed_ce)``, ``transpose(jvp(...))``), so
        # every op of either pass carries the words in its op_name
        with jax.named_scope(CE_SCOPE):
            loss = _flce(h2, w, safe, valid, int(block_size), cdt,
                         _forward_interpret(h2, w, cdt))         # [N] f32
        if maybe_mask:
            mflat = maybe_mask[0].reshape(N).astype(jnp.float32)
            return jnp.sum(loss * mflat) / jnp.maximum(jnp.sum(mflat), 1.0)
        denom = jnp.sum(valid.astype(jnp.float32))
        return jnp.sum(loss) / jnp.maximum(denom, 1.0)

    args = [hidden, weight, label] + ([loss_mask] if loss_mask is not None
                                      else [])
    return op("fused_linear_cross_entropy", _primal, args)

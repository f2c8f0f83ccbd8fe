"""Ring attention — context parallelism for long sequences.

Reference note: the reference (SURVEY.md §5.7) has NO sequence/context
parallelism; this is a beyond-parity capability.  Design follows the ring
attention construction (Liu et al. 2023; the blockwise-parallel form of
flash attention): the sequence axis is sharded over the mesh axis "sep",
every device keeps its Q chunk resident and the K/V chunks circulate around
the ring with `lax.ppermute` (ICI neighbor hops — bandwidth-optimal, no
all-gather), while an online-softmax accumulator (m, l, o) absorbs one K/V
block per tick.

Causal handling: tick r on device i sees key block j = (i - r) mod p.
Tick 0 is the diagonal (j == i) — processed FIRST so the running max is
always finite before any fully-masked block arrives (whose -1e30 scores
then underflow to exactly zero probability).  Blocks with j > i are
entirely in the future and contribute nothing; blocks j < i attend fully.

The whole ring is one differentiable op: the backward of the scan re-runs
the ring with transposed ppermutes (jax autodiff of shard_map), matching
the memory profile of blockwise attention (no [S, S] matrix ever exists).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import apply_op
from ..distributed import mesh as mesh_mod

__all__ = ["ring_flash_attention"]

SEP_AXIS = "sep"


def _varying(x, axis):
    return jax.lax.pcast(x, (axis,), to="varying")


def _ring_inner(q_l, k_l, v_l, p: int, s_local: int, scale: float,
                is_causal: bool):
    """One device's ring loop.  q_l/k_l/v_l: [B, s, H, D] local chunks."""
    i = jax.lax.axis_index(SEP_AXIS)
    B, s, H, D = q_l.shape
    qf = q_l.astype(jnp.float32)
    o0 = _varying(jnp.zeros((B, H, s, D), jnp.float32), SEP_AXIS)
    m0 = _varying(jnp.full((B, H, s), -jnp.inf, jnp.float32), SEP_AXIS)
    l0 = _varying(jnp.zeros((B, H, s), jnp.float32), SEP_AXIS)
    qa = jnp.arange(s)
    ka = jnp.arange(s)
    perm = [(t, (t + 1) % p) for t in range(p)]

    def tick(carry, r):
        o, m, l, k_c, v_c = carry
        j = (i - r) % p
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_c.astype(jnp.float32)) * scale
        if is_causal:
            qpos = i * s_local + qa
            kpos = j * s_local + ka
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        bm = jnp.max(scores, axis=-1)                      # [B,H,s]
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(scores - m_new[..., None])          # [B,H,sq,sk]
        l_new = l * alpha + pexp.sum(-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", pexp,
                        v_c.astype(jnp.float32))
        o_new = o * alpha[..., None] + pv
        k_n = jax.lax.ppermute(k_c, SEP_AXIS, perm)
        v_n = jax.lax.ppermute(v_c, SEP_AXIS, perm)
        return (o_new, m_new, l_new, k_n, v_n), None

    (o, m, l, _, _), _ = jax.lax.scan(
        tick, (o0, m0, l0, k_l, v_l), jnp.arange(p))
    out = o / jnp.maximum(l, 1e-30)[..., None]             # [B,H,s,D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q_l.dtype)


def ring_flash_attention(query, key, value, is_causal: bool = True,
                         mesh=None, name=None):
    """Causal attention over [B, S, H, D] with S sharded over "sep".

    Falls back to the plain flash/XLA path when no sep axis is active or
    the sequence doesn't divide it (callers: ops.pallas.flash_attention).
    """
    m = mesh or mesh_mod.get_global_mesh()
    p = m.shape.get(SEP_AXIS, 1) if m is not None else 1
    S = query.shape[1]
    if p <= 1 or S % p != 0:
        from .pallas import flash_attention

        return flash_attention(query, key, value, is_causal=is_causal,
                               dropout_p=0.0, training=False)
    s_local = S // p
    D = query.shape[-1]
    scale = 1.0 / (D ** 0.5)

    def _primal(q, k, v):
        spec = P(None, SEP_AXIS, None, None)
        f = jax.shard_map(
            lambda ql, kl, vl: _ring_inner(ql, kl, vl, p, s_local, scale,
                                           is_causal),
            mesh=m, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={SEP_AXIS})
        return f(q, k, v)

    return apply_op("ring_flash_attention", _primal, [query, key, value])

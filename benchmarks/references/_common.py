"""What both plain references share: float32 arithmetic at ``highest``
matmul precision, the optional lower-precision control, and the reading of a
served sequence.  Plain ``jax.numpy``; imports nothing of the program."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def fake_fp8(a, axis):
    """``a`` rounded to an 8-bit float (4 exponent bits, 3 mantissa bits)
    under one scale per slice along ``axis`` (a token row, an output column),
    returned as float32: the control's lower precision, the step below the
    bfloat16 that the configurations state.  The backward pass sees the
    identity (straight-through), as an fp8 training path would arrange."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 128.0
    s = jnp.where(s == 0, 1.0, s)
    q = jax.lax.reduce_precision(a / s, exponent_bits=4, mantissa_bits=3) * s
    return a + jax.lax.stop_gradient(q - a)


def mm(a, b, control: bool):
    """``a @ b`` in float32 at the highest precision; under ``control`` both
    operands first go through fp8 (per row of ``a``, per column of ``b``)."""
    a, b = a.astype(F32), b.astype(F32)
    if control:
        a, b = fake_fp8(a, -1), fake_fp8(b, 0)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def causal_attention(q, k, v):
    """``q [S,H,D]``, ``k``/``v [S,H,D]`` (already expanded to H heads) ->
    ``[S,H,D]``; softmax in float32 over the causal window."""
    S, _H, D = q.shape
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(F32(D))
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("rows",))
def rows_from(h, start, rows: int):
    """``rows`` consecutive rows of ``h [S, n]`` from ``start`` (clamped)."""
    return jax.lax.dynamic_slice_in_dim(h, start, rows, axis=0)

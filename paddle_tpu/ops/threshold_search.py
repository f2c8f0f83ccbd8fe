"""Cut-offs in the order of a row, searched and not sorted for.

The k-th largest value of a row is the largest ``v`` that ``k`` or more of
its entries reach.  On ``uint32`` keys whose unsigned order is the floats'
order that ``v`` is built bit by bit from the top: 32 fused
compare-and-reduce passes over the rows, exact, entries tied with the cut
all at or above it, and no order over the row (PR 29 measured the sort of
32 x 50,304 logits at 1.8 ms on a v5e and this search at 0.06).  The
sampler's ``top_k`` / ``top_p`` cut-offs (``serving/sampling.py``) and the
sparse-attention indexer's per-query cut (``ops/pallas/dsa_attention_kernel``)
are both this search.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["order_keys", "key_values", "largest_key", "kth_largest_key"]


def order_keys(z):
    """``uint32`` keys whose unsigned order is the float order of ``z``
    (``-0.0`` counted as ``0.0``)."""
    b = jax.lax.bitcast_convert_type(jnp.where(z == 0.0, 0.0, z),
                                     jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def key_values(keys):
    """The float32 values :func:`order_keys` made ``keys`` from."""
    top = jnp.uint32(1 << 31)
    return jax.lax.bitcast_convert_type(
        jnp.where(keys >> 31 == 1, keys ^ top, ~keys), jnp.float32)


def largest_key(holds, rows: int):
    """Per row the largest ``uint32`` ``v`` with ``holds(v)`` (``[rows]``
    keys → ``[rows]`` bool, true up to some ``v`` and false past it; 0
    where it never holds), built bit by bit from the top: 32 fused
    compare-and-reduce passes, and no order over the row."""
    def bit(i, v):
        up = v | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(holds(up), up, v)
    return jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32))


def kth_largest_key(keys, k):
    """Per row of ``keys [N, T]`` the key of its ``k``-th largest entry
    (``k`` a scalar or ``[N]``): the largest ``v`` that ``k`` or more
    entries reach (a row shorter than ``k``: its smallest)."""
    k = jnp.broadcast_to(jnp.minimum(jnp.asarray(k, jnp.int32),
                                     keys.shape[1]), keys.shape[:1])
    return largest_key(
        lambda v: jnp.sum(keys >= v[:, None], axis=-1, dtype=jnp.int32) >= k,
        keys.shape[0])

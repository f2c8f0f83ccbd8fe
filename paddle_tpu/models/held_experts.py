"""What the expert-layer decoders share (``deepseek_v3.py``, ``keye_vl2.py``):
the dropless layer of the experts a chip holds, and the small pieces both
families build their blocks from.

An expert layer is **told which experts it holds** (``held = (start, stop)``
of the router's outputs): it routes over all of them, normalises over all
``k`` chosen, and computes its own experts' terms only; what the absent
experts would add is left out (nothing stands in for other chips).
**Dropless**: every assignment of a live token to a held expert is computed,
by one grouped matmul over the tokens sorted by expert
(``ops/pallas/moe_kernel.py``) against stacked weights ``[held, h, 2 f]`` and
``[held, f, h]`` that no step stacks or copies.  How a family scores and
weighs its experts (sigmoid with a selection bias, softmax renormalised) is
the family's own ``route``.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core import rng as rng_mod
from ..core import dtype as dtype_mod
from ..nn import initializer as I

#: named scopes of an expert layer's work in a compiled program's op names
ROUTE_SCOPE = "moe.route"
EXPERTS_SCOPE = "moe.experts"
#: and of what every decoder does outside its layers (``gpt.py`` too): the
#: token embedding, and the final norm to the logits
EMBED_SCOPE = "model.embed"
HEAD_SCOPE = "model.head"

F32 = jnp.float32


class _Normal(I.Initializer):
    """normal(0, std) drawn in the dtype it is asked for."""

    def __init__(self, std: float):
        self.std = std

    def __call__(self, shape, dtype):
        dt = dtype_mod.convert_dtype(dtype)
        return jax.random.normal(rng_mod.next_key(), tuple(shape), dt) \
            * jnp.asarray(self.std, dt)


def _interpret() -> bool:
    from ..ops.pallas import use_pallas

    return not use_pallas()


def _rms(x, g, eps):
    """RMSNorm in float32; the result in the gain's dtype (the dtype the
    next matmul's weights are in)."""
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * g.astype(F32)).astype(g.dtype)


def _swiglu(x, w_gu, w_d):
    """``(silu(x W_g) * x W_u) W_d``; the result float32 (it is added to the
    residual stream)."""
    gu = jnp.dot(x, w_gu)
    f = gu.shape[-1] // 2
    return jnp.dot(jax.nn.silu(gu[..., :f]) * gu[..., f:], w_d,
                   preferred_element_type=F32)


def held_experts_forward(x, chosen, weights, live, w_gu, w_d, *,
                         held: Tuple[int, int], interpret: bool):
    """The held experts' part of the layer's output for ``x [T, h]``
    (float32), and the load: ``(y [T, h], assignments_held,
    experts_touched)``.  Every
    assignment of a live token to a held expert is computed; the others
    add nothing."""
    from ..ops.pallas.moe_kernel import moe_grouped_matmul

    T, k = chosen.shape
    G = held[1] - held[0]
    local = chosen - held[0]
    mine = (local >= 0) & (local < G) & live[:, None]
    key = jnp.where(mine, local, G).reshape(-1)       # G = "not here", last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
    rows = jnp.take(x, order // k, axis=0)            # [T*k, h] by expert
    gu = moe_grouped_matmul(rows, w_gu, sizes, interpret=interpret)
    f = gu.shape[-1] // 2
    y = moe_grouped_matmul(jax.nn.silu(gu[:, :f]) * gu[:, f:], w_d, sizes,
                           interpret=interpret)
    # back to token order: row ``back[t * k + j]`` is token t's j-th choice
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(T, k, -1)
    y = jnp.einsum("tkh,tk->th", y.astype(F32),
                   jnp.where(mine, weights, 0.0))
    return y, jnp.sum(sizes), jnp.sum(sizes > 0)



"""Fused TPU kernels (Pallas/Mosaic) with XLA reference fallbacks.

Reference parity: the hand-fused CUDA kernel set in
``paddle/fluid/operators/fused/`` (fused_attention_op.cu, fused_feedforward,
fused_bias_dropout_residual_layer_norm) — re-designed as Pallas TPU kernels,
not translations.  Every kernel has a pure-XLA reference implementation used
(a) on CPU/test backends, (b) as the numerics oracle in tests.

Selection: ``use_pallas()`` is True only on a real TPU backend; elsewhere the
XLA fallback runs (and XLA fuses it well enough for tests).  Which path a
program took is readable from its HLO: each path traces under its own
``jax.named_scope`` (``ATTN_SCOPE_PALLAS`` / ``ATTN_SCOPE_XLA``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.dispatch import apply_op
from ...core import rng as rng_mod


#: named scopes the two self-attention paths trace under, so the path a
#: compiled program took shows in the op names of its HLO
ATTN_SCOPE_PALLAS = "attention.pallas_flash"
ATTN_SCOPE_XLA = "attention.xla_sdpa"


def per_shard(kernel, args, specs, mesh):
    """Call ``kernel(*args)`` once per shard of ``mesh``; the result has
    the shape and the spec of the first argument.

    GSPMD cannot partition a Mosaic kernel: any program over more than one
    device that contains one fails to lower with "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" (first
    met on the four-chip host, PR 21).  Attention is independent per batch
    row and per head, so under a multi-device mesh the kernel runs inside
    a ``shard_map`` that is manual over EVERY mesh axis (Mosaic accepts
    nothing less), each shard on its own slice.

    ``specs`` name the mesh axes each argument dimension may split over;
    axes the mesh lacks and entries that do not divide their dimension
    fall back to replication (every shard of that axis then computes the
    same slice).  With no mesh, or one device, the kernel is called
    directly."""
    from jax.sharding import PartitionSpec as P

    from ...distributed.sharding_spec import _divisible, _filter_spec

    if mesh is None or mesh.size == 1:
        return kernel(*args)
    fitted = []
    for spec, a in zip(specs, args):
        spec = _filter_spec(spec, mesh)
        fitted.append(P(*(e if _divisible((d,), P(e), mesh) else None
                          for d, e in zip(a.shape, spec))))
    return jax.shard_map(kernel, mesh=mesh, in_specs=tuple(fitted),
                         out_specs=fitted[0], check_vma=False)(*args)


@functools.lru_cache(maxsize=1)
def use_pallas() -> bool:
    """True when the default backend is TPU hardware.  A backend that
    fails to initialise raises here; it is never read as "no TPU"."""
    return jax.default_backend() == "tpu"


def _sdpa_reference(q, k, v, mask, dropout_key, dropout_p, is_causal):
    """XLA attention oracle. q/k/v: [B, S, H, D] (paddle fused_attention layout)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    # [B, H, Sq, Sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if is_causal:
        causal = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq)
        logits = jnp.where(causal[None, None], logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_on_mesh(q, k, v, causal, interpret=False):
    """The Pallas flash kernel over ``[B, S, H, D]``, one call per
    (batch, heads) shard of the global mesh — see :func:`per_shard`."""
    from jax.sharding import PartitionSpec as P

    from ...distributed import mesh as _mesh_mod
    from ...distributed.sharding_spec import BATCH_AXES, MODEL_AXIS
    from .flash_attention_kernel import flash_attention_fused

    spec = P(BATCH_AXES, None, MODEL_AXIS, None)
    return per_shard(
        functools.partial(flash_attention_fused, causal=causal,
                          interpret=interpret),
        (q, k, v), (spec, spec, spec), _mesh_mod.get_global_mesh())


def flash_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                    is_causal=False, training=True, name=None):
    """Flash attention over [B, S, H, D] tensors.

    On TPU this dispatches to the Pallas kernel (flash_attention_kernel.py);
    on other
    backends it runs the XLA oracle.  Autograd flows through jax.vjp either
    way (the Pallas path defines a custom_vjp with its own backward kernel).
    """
    p = dropout_p if training else 0.0
    key_arr = rng_mod.next_key() if p > 0.0 else None

    if attn_mask is None and p == 0.0:
        # context parallelism: with a live "sep" axis the sequence is
        # sharded — run the ppermute ring instead of letting GSPMD
        # all-gather K/V (ops/ring_attention.py; beyond-reference)
        from ...distributed import mesh as _mesh_mod

        _m = _mesh_mod.get_global_mesh()
        if _m is not None and _m.shape.get("sep", 1) > 1 \
                and query.shape[1] % _m.shape["sep"] == 0 \
                and query.shape[1] == key.shape[1]:
            from ..ring_attention import ring_flash_attention

            return ring_flash_attention(query, key, value,
                                        is_causal=is_causal, mesh=_m)

    if use_pallas() and attn_mask is None and p == 0.0:
        from .flash_attention_kernel import supports

        if supports(tuple(query.shape), tuple(key.shape)):
            def _primal(q, k, v):
                with jax.named_scope(ATTN_SCOPE_PALLAS):
                    return flash_on_mesh(q, k, v, causal=is_causal)

            return apply_op("flash_attention", _primal, [query, key, value])

    def _primal(q, k, v, *extra):
        i = 0
        m = None
        dk = None
        if attn_mask is not None:
            m = extra[i]; i += 1
        if key_arr is not None:
            dk = extra[i]; i += 1
        with jax.named_scope(ATTN_SCOPE_XLA):
            return _sdpa_reference(q, k, v, m, dk, p, is_causal)

    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    if key_arr is not None:
        args.append(key_arr)
    return apply_op("flash_attention", _primal, args)


def fused_bias_dropout_residual_layer_norm(x, residual, bias=None,
                                           ln_scale=None, ln_bias=None,
                                           dropout_rate=0.5, ln_epsilon=1e-5,
                                           training=True, name=None):
    """out = LayerNorm(residual + dropout(x + bias)) (reference:
    fused_bias_dropout_residual_layer_norm_op semantics)."""
    p = dropout_rate if training else 0.0
    key_arr = rng_mod.next_key() if p > 0.0 else None

    def _primal(a, res, *extra):
        i = 0
        if bias is not None:
            a = a + extra[i]; i += 1
        if key_arr is not None:
            keep = jax.random.bernoulli(extra[i], 1.0 - p, a.shape)
            a = jnp.where(keep, a / (1.0 - p), 0.0)
            i += 1
        y = res + a
        mean = jnp.mean(y, axis=-1, keepdims=True)
        var = jnp.var(y, axis=-1, keepdims=True)
        out = (y - mean) * jax.lax.rsqrt(var + ln_epsilon)
        if ln_scale is not None:
            out = out * extra[i]; i += 1
        if ln_bias is not None:
            out = out + extra[i]; i += 1
        return out

    args = [x, residual]
    if bias is not None:
        args.append(bias)
    if key_arr is not None:
        args.append(key_arr)
    if ln_scale is not None:
        args.append(ln_scale)
    if ln_bias is not None:
        args.append(ln_bias)
    return apply_op("fused_bias_dropout_residual_ln", _primal, args)


def rotary_embedding(q, k, cos, sin, position_ids=None):
    """Apply rotary position embedding to q/k ([B, S, H, D]).

    ``position_ids`` (``[B, S]`` int, optional) selects per-token rows of
    the cos/sin tables instead of assuming positions ``0..S-1`` — the
    position-offset path KV-cache decode needs (each slot's single query
    token sits at that slot's own sequence offset).  The rotation is
    computed in the tables' float32 and returned in the inputs' dtype.
    """

    def _rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], axis=-1)

    if position_ids is not None:
        def _primal_pos(qa, ka, c, s, pos):
            # c/s: [T, D] tables gathered at pos [B, S] → [B, S, 1, D]
            c_b = c[pos][:, :, None, :]
            s_b = s[pos][:, :, None, :]
            q_out = qa * c_b + _rot(qa) * s_b
            k_out = ka * c_b + _rot(ka) * s_b
            return q_out.astype(qa.dtype), k_out.astype(ka.dtype)

        return apply_op("rotary_embedding", _primal_pos,
                        [q, k, cos, sin, position_ids], n_outs=2)

    def _primal(qa, ka, c, s):
        # c/s: [S, D] → broadcast over batch/heads
        c_b = c[None, :, None, :]
        s_b = s[None, :, None, :]
        q_out = qa * c_b + _rot(qa) * s_b
        k_out = ka * c_b + _rot(ka) * s_b
        # the float32 tables promote: back to the activations' dtype, so that
        # bf16 queries reach the paged kernels as bf16 (float32 ones of 32
        # heads x 128 overflowed the prefill kernel's VMEM on a v5e)
        return q_out.astype(qa.dtype), k_out.astype(ka.dtype)

    return apply_op("rotary_embedding", _primal, [q, k, cos, sin], n_outs=2)

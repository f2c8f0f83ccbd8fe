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


def per_shard(kernel, args, specs, mesh, out=None):
    """Call ``kernel(*args)`` once per shard of ``mesh``; the result has
    the shape and the spec of the first argument, or the specs that
    ``out(specs as fitted)`` gives.

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
                         out_specs=out(fitted) if out else fitted[0],
                         check_vma=False)(*args)


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


def _flash_per_shard(kernel, args, causal, interpret):
    from jax.sharding import PartitionSpec as P

    from ...distributed import mesh as _mesh_mod
    from ...distributed.sharding_spec import BATCH_AXES, MODEL_AXIS

    spec = P(BATCH_AXES, None, MODEL_AXIS, None)
    return per_shard(
        functools.partial(kernel, causal=causal, interpret=interpret),
        args, (spec,) * len(args), _mesh_mod.get_global_mesh())


def flash_on_mesh(q, k, v, causal, interpret=False):
    """The Pallas flash kernel over ``[B, S, H, D]``, one call per
    (batch, heads) shard of the global mesh — see :func:`per_shard`."""
    from .flash_attention_kernel import flash_attention_fused

    return _flash_per_shard(flash_attention_fused, (q, k, v), causal,
                            interpret)


def flash_qkv_on_mesh(qkv, causal, interpret=False):
    """The same kernels reading a head-major fused projection
    ``[B, S, H, 3·D]`` as it is (``[B, S, H, D]`` comes back), under the
    same split of batch and heads."""
    from .flash_attention_kernel import flash_attention_fused_qkv

    return _flash_per_shard(flash_attention_fused_qkv, (qkv,), causal,
                            interpret)


def streamed_ce_stats_on_mesh(h, w, labels, interpret=False):
    """The streamed CE's forward kernel over ``h [N, H]``, ``w [V, H]`` and
    ``labels [N]`` → ``(lse, picked)``, ``[N]`` each: one call per shard of
    the global mesh, the rows split over its data axes, the weight whole on
    every shard — see :func:`per_shard`."""
    from jax.sharding import PartitionSpec as P

    from ...distributed import mesh as _mesh_mod
    from ...distributed.sharding_spec import BATCH_AXES
    from .streamed_ce_kernel import streamed_ce_stats

    return per_shard(
        functools.partial(streamed_ce_stats, interpret=interpret),
        (h, w, labels), (P(BATCH_AXES, None), P(None, None), P(BATCH_AXES)),
        _mesh_mod.get_global_mesh(),
        out=lambda fitted: (P(fitted[0][0]),) * 2)


def _ring_mesh(q_len, k_len):
    """The global mesh where its live "sep" axis shards this sequence (the
    ring's case), else None."""
    from ...distributed import mesh as _mesh_mod

    _m = _mesh_mod.get_global_mesh()
    if _m is not None and _m.shape.get("sep", 1) > 1 \
            and q_len % _m.shape["sep"] == 0 and q_len == k_len:
        return _m
    return None


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
        _m = _ring_mesh(query.shape[1], key.shape[1])
        if _m is not None:
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


def flash_attention_qkv(qkv, dropout_p=0.0, is_causal=False, training=True):
    """Self-attention of a caller that holds a head-major fused projection
    ``[B, S, H, 3·D]`` (a head's q, k and v side by side) → ``[B, S, H, D]``.

    Where :func:`flash_attention` would run the Pallas kernels, they read
    q, k and v out of the projection as it is and write its gradient as one
    array: no split, no join, no transpose stands between the projections'
    matmuls and the kernels.  Everywhere else (another backend, dropout, a
    sequence the kernels refuse, a live "sep" axis) the three are split off
    and take :func:`flash_attention`'s path."""
    B, S, H, D3 = qkv.shape
    heads = (B, S, H, D3 // 3)
    p = dropout_p if training else 0.0
    if p == 0.0 and use_pallas() and _ring_mesh(S, S) is None:
        from .flash_attention_kernel import supports

        if supports(heads, heads):
            def _primal(x):
                with jax.named_scope(ATTN_SCOPE_PALLAS):
                    return flash_qkv_on_mesh(x, causal=is_causal)

            return apply_op("flash_attention", _primal, [qkv])
    q, k, v = qkv.split(3, axis=-1)
    return flash_attention(q, k, v, dropout_p=dropout_p, is_causal=is_causal,
                           training=training)


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

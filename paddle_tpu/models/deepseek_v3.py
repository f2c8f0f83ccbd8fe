"""DeepSeek-V3-shaped decoder: latent attention (MLA) and sigmoid-routed
experts with a shared one.  The layer code of a family of configurations
(the configuration names the model); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through and an untied
head, like ``llama.py``; what differs:

- **Latent attention.**  ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` ->
  ``[q_nope | q_rope]`` per head; ``[c | k_r] = x W_kva``, ``c_kv = norm(c)``,
  ``k_rope = rope(k_r)`` — one rotary head shared by all.  What a token
  leaves in the cache is the ONE vector ``[c_kv | k_rope]`` (after the norm
  and the rotation).  ``W^K [H, nope, rank]`` and ``W^V [H, rank, v]`` are
  the two halves of the published ``kv_b_proj``, stored per head so that no
  step slices or transposes it, and **which side they are applied on
  follows from where a key lies**.  A decode step, and a forward with no
  cache, are **absorbed**: ``q_lat = [q_nope W^K | q_rope]`` against the
  latent vector, ``o = (softmax . c_kv) W^V`` — one query row a head, the
  keys read once as they are stored.  A tail prefill has its own tail's
  latents in its hands, so it **up-projects** them once a layer (``K =
  c_kv W^K``, ``V = c_kv W^V``) and the tail attends to itself with
  ``nope + rope``-wide keys and ``v``-wide values (3.4 x fewer operations
  a pair at the published widths); only the cached prefix, which lies in
  the pool as latents, stays absorbed, and the two parts are merged by
  their softmax statistics.  Decode and tail prefill go through the cache
  context's latent calls (the Pallas kernels of
  ``ops/pallas/mla_attention_kernel.py`` over the paged pool); every form
  is the same mathematics.
- **Rotary**, ``rope_interleave``: the pairs ``(2i, 2i+1)`` are de-interleaved
  to halves, then rotate-half, at absolute positions; angles are computed
  from the positions in float32 (no table is baked into a compiled step) and
  the result is cast back to the activations' dtype.
- **Experts** (layers from ``first_k_dense_replace`` on).  The router is
  float32 from the hidden state: ``s = sigmoid(x W_r)``, the top ``k`` of
  ``s + b`` (``e_score_correction_bias``), weights ``s[chosen] / sum * scale``.
  The layer is **told which experts it holds** (``held_experts = (start,
  stop)`` of ``n_routed_experts``): it routes over all of them, normalises
  over all ``k`` chosen, and computes its own experts' terms only; what the
  absent experts would add is left out (nothing stands in for other chips).
  **Dropless**: every assignment to a held expert is computed, by one
  grouped matmul over the tokens sorted by expert
  (``ops/pallas/moe_kernel.py``) against stacked weights ``[held, h, 2 f]``
  and ``[held, f, h]`` that no step stacks or copies.  The shared expert is a
  plain SwiGLU beside them.
- Parameters are created in ``config.dtype``: no float32 copy of the model
  exists at any time.  Matmul operands are in that dtype; the residual
  stream, the norms, the router, the softmax statistics and the logits are
  float32.
- The model states the cache it needs (:meth:`cache_spec`: one side, one
  "head", ``kv_lora_rank + qk_rope_head_dim`` wide).  No multi-token
  prediction module.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.container import LayerList
from ..ops.pallas.mla_attention_kernel import ABSORB_SCOPE, absorb_queries
from .held_experts import (EMBED_SCOPE, EXPERTS_SCOPE, F32,  # noqa: F401
                           HEAD_SCOPE, ROUTE_SCOPE, held_experts_forward,
                           _interpret, _Normal, _rms, _swiglu)


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    #: None: one query projection, no low-rank pair and no norm between
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 7168            # the leading dense layers' MLP
    moe_intermediate_size: int = 768
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256              # the router's outputs
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    #: ``(start, stop)`` of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 32e6
    #: no rotation at all: the ``qk_rope_head_dim`` columns of a query and
    #: the shared key part beside the latent are used as projected
    mla_use_nope: bool = False
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.held_experts) if self.held_experts is not None \
            else (0, self.n_routed_experts)


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """The CPU tests' preset: every mechanism, toy widths."""
    for k, v in dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4, held_experts=(0, 4),
            max_position_embeddings=256).items():
        kw.setdefault(k, v)
    return DeepseekV3Config(**kw)


def _rope(x, pos, theta: float):
    """Interleaved rotary on ``x [B, S, ..., D]`` at positions ``pos [B, S]``:
    pairs ``(2i, 2i+1)`` to halves, then rotate-half; float32 inside, the
    input's dtype out."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos.astype(F32)[..., None] * inv                   # [B, S, D/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(F32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def causal_latent_attention(q_lat, lat, *, scale: float, dv: int):
    """Absorbed attention with no cache: ``q_lat [B, S, H, W]`` against the
    sequence's own latents ``lat [B, S, W]``; one masked softmax in float32."""
    S = q_lat.shape[1]
    s = jnp.einsum("bqhw,bkw->bhqk", q_lat, lat,
                   preferred_element_type=F32) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhqk,bkv->bqhv", p, lat[..., :dv],
                      preferred_element_type=F32).astype(q_lat.dtype)


class DeepseekV3Attention(Layer):
    """Latent attention of any configuration that names the widths the way
    :class:`DeepseekV3Config` does (``kimi_linear.py``'s does).  Two cases
    beside the published DeepSeek-V3 form: ``q_lora_rank=None`` has one
    ``q_proj`` in place of ``q_a_proj``/``q_a_layernorm``/``q_b_proj``, and
    ``mla_use_nope`` rotates nothing — the ``rope``-wide columns are one
    more key part that every head shares."""

    def __init__(self, c: DeepseekV3Config):
        super().__init__()
        self.c = c
        H, h = c.num_attention_heads, c.hidden_size
        init = _Normal(c.initializer_range)

        def mat(*shape):
            return self.create_parameter(list(shape), dtype=c.dtype,
                                         default_initializer=init)

        def gain(n):
            return self.create_parameter([n], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        if c.q_lora_rank is None:
            self.q_proj = mat(h, H * c.qk_head_dim)
        else:
            self.q_a_proj = mat(h, c.q_lora_rank)
            self.q_a_layernorm = gain(c.q_lora_rank)
            self.q_b_proj = mat(c.q_lora_rank, H * c.qk_head_dim)
        self.kv_a_proj_with_mqa = mat(h, c.latent_dim)
        self.kv_a_layernorm = gain(c.kv_lora_rank)
        # the published kv_b_proj [rank, H * (nope + v)], per head and in
        # the two halves the absorbed form multiplies by
        self.w_uk = mat(H, c.qk_nope_head_dim, c.kv_lora_rank)
        self.w_uv = mat(H, c.kv_lora_rank, c.v_head_dim)
        self.o_proj = mat(H * c.v_head_dim, h)

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, _ = x.shape
        H, rank = c.num_attention_heads, c.kv_lora_rank
        if cache_ctx is None:
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                   (B, S))
        elif cache_ctx.mode == "prefill":
            pos = cache_ctx.prefill_positions(S)
            pos = jnp.arange(S, dtype=jnp.int32)[None] if pos is None \
                else pos._value()
        elif cache_ctx.mode == "decode":
            pos = cache_ctx.positions()._value()
        else:
            raise ValueError(f"latent attention has no {cache_ctx.mode!r} "
                             f"form")
        if c.q_lora_rank is None:
            q = jnp.dot(x, self.q_proj._value())
        else:
            c_q = _rms(jnp.dot(x, self.q_a_proj._value()),
                       self.q_a_layernorm._value(), c.rms_norm_eps)
            q = jnp.dot(c_q, self.q_b_proj._value())
        q = q.reshape(B, S, H, c.qk_head_dim)
        q_nope = q[..., :c.qk_nope_head_dim]
        q_rope = q[..., c.qk_nope_head_dim:]
        ckr = jnp.dot(x, self.kv_a_proj_with_mqa._value())
        c_kv = _rms(ckr[..., :rank], self.kv_a_layernorm._value(),
                    c.rms_norm_eps)
        k_rope = ckr[..., rank:]
        if not c.mla_use_nope:
            q_rope = _rope(q_rope, pos, c.rope_theta)
            k_rope = _rope(k_rope, pos, c.rope_theta)
        lat = jnp.concatenate([c_kv, k_rope], axis=-1)        # [B, S, W]
        scale = c.qk_head_dim ** -0.5
        if cache_ctx is not None and cache_ctx.mode == "prefill":
            # the tail's own keys up-projected, the cached prefix absorbed
            cache_ctx.write_prefill_latent(Tensor._wrap(lat))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)    # [B, S, H, D]
            o = cache_ctx.latent_prefill_attention(
                Tensor._wrap(q), Tensor._wrap(lat), self.w_uk._value(),
                self.w_uv._value(), scale=scale)._value()
        else:
            with jax.named_scope(ABSORB_SCOPE):
                q_lat = absorb_queries(q_nope, q_rope, self.w_uk._value())
            kw = dict(scale=scale, dv=rank)
            if cache_ctx is None:
                o_lat = causal_latent_attention(q_lat, lat, **kw)
            else:
                o_lat = cache_ctx.latent_decode_attention(
                    Tensor._wrap(q_lat), Tensor._wrap(lat), **kw)._value()
            with jax.named_scope(ABSORB_SCOPE):
                o = jnp.einsum("bshr,hrv->bshv", o_lat, self.w_uv._value())
        return jnp.dot(o.reshape(B, S, H * c.v_head_dim),
                       self.o_proj._value(), preferred_element_type=F32)


class DeepseekV3MLP(Layer):
    """SwiGLU, gate and up side by side in one matrix."""

    def __init__(self, c: DeepseekV3Config, width: int):
        super().__init__()
        init = _Normal(c.initializer_range)
        self.gate_up_proj = self.create_parameter(
            [c.hidden_size, 2 * width], dtype=c.dtype,
            default_initializer=init)
        self.down_proj = self.create_parameter(
            [width, c.hidden_size], dtype=c.dtype, default_initializer=init)

    def forward(self, x):
        return _swiglu(x, self.gate_up_proj._value(), self.down_proj._value())


def route(x, w_r, bias, *, top_k: int, scale: float, eps: float = 1e-20):
    """``(chosen [T, k], weights [T, k])``: sigmoid scores in float32, the
    top ``k`` of score + bias, weights normalised over the chosen (their sum
    ``+ eps``: a family's published constant) and scaled."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), w_r.astype(F32),
                               precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + bias.astype(F32)[None, :], top_k)
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + eps) * scale


class DeepseekV3MoE(Layer):
    def __init__(self, c: DeepseekV3Config):
        super().__init__()
        self.c = c
        init = _Normal(c.initializer_range)
        G = c.held[1] - c.held[0]
        h, f = c.hidden_size, c.moe_intermediate_size
        self.gate = self.create_parameter(
            [h, c.n_routed_experts], dtype=c.dtype, default_initializer=init)
        self.register_buffer("e_score_correction_bias", Tensor._wrap(
            jnp.zeros((c.n_routed_experts,), F32)))
        self.experts_gate_up = self.create_parameter(
            [G, h, 2 * f], dtype=c.dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [G, f, h], dtype=c.dtype, default_initializer=init)
        self.shared_experts = DeepseekV3MLP(c, f * c.n_shared_experts)

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, h = x.shape
        flat = x.reshape(B * S, h)
        live = jnp.ones((B * S,), bool) if cache_ctx is None \
            else cache_ctx.live_tokens(S).reshape(-1)
        with jax.named_scope(ROUTE_SCOPE):
            chosen, weights = route(
                flat, self.gate._value(),
                self.e_score_correction_bias._value(),
                top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor)
        with jax.named_scope(EXPERTS_SCOPE):
            y, n_held, n_touched = held_experts_forward(
                flat, chosen, weights, live, self.experts_gate_up._value(),
                self.experts_down._value(), held=c.held,
                interpret=_interpret())
        if cache_ctx is not None:
            cache_ctx.note_experts(n_held, n_touched)
        return y.reshape(B, S, h) + self.shared_experts(x)


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, c: DeepseekV3Config, index: int):
        super().__init__()
        self.eps = c.rms_norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        self.input_layernorm = gain()
        self.self_attn = DeepseekV3Attention(c)
        self.post_attention_layernorm = gain()
        self.is_moe = index >= c.first_k_dense_replace
        self.mlp = DeepseekV3MoE(c) if self.is_moe \
            else DeepseekV3MLP(c, c.intermediate_size)

    def forward(self, x, cache_ctx=None):
        x = x + self.self_attn(
            _rms(x, self.input_layernorm._value(), self.eps), cache_ctx)
        m = _rms(x, self.post_attention_layernorm._value(), self.eps)
        return x + (self.mlp(m, cache_ctx) if self.is_moe else self.mlp(m))


class DeepseekV3Model(Layer):
    def __init__(self, c: DeepseekV3Config):
        super().__init__()
        self.c = c
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([DeepseekV3DecoderLayer(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))

    def forward(self, input_ids, cache_ctx=None):
        """``input_ids [B, S]`` (raw) -> final hidden states ``[B, S, h]``
        (raw, float32, not yet normed)."""
        # the residual stream is float32: every sublayer reads it through a
        # norm (cast to the weights' dtype) and adds a float32 result, so
        # the stream's own rounding does not pile up layer by layer and
        # reach the router, whose top-k flips on a near tie
        with jax.named_scope(EMBED_SCOPE):
            h = jnp.take(self.embed_tokens._value(), input_ids, axis=0
                         ).astype(F32)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
            h = layer(h, cache_ctx)
        return h


class DeepseekV3ForCausalLM(Layer):
    """The decoder, the final norm and an untied head; logits float32."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=_Normal(config.initializer_range))

    def cache_spec(self):
        """One side, one "head": the latent vector ``[c_kv | k_rope]``."""
        from ..serving.kv_cache import CacheSpec

        return CacheSpec.latent(self.config.num_hidden_layers,
                                self.config.latent_dim)

    def forward(self, input_ids, cache_ctx=None):
        ids = input_ids._value() if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        h = self.model(ids.astype(jnp.int32), cache_ctx)
        with jax.named_scope(HEAD_SCOPE):
            if cache_ctx is not None:
                # prefill: the head sees the one row the engine samples from
                h = cache_ctx.select_last(Tensor._wrap(h))._value()
            h = _rms(h, self.model.norm._value(), self.config.rms_norm_eps)
            return Tensor._wrap(jnp.dot(h, self.lm_head._value(),
                                        preferred_element_type=F32))

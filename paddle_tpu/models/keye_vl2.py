"""Keye-VL-2.0-shaped decoder (the language model): grouped-query attention
under a learned **indexer** that picks the cached tokens a query attends to,
three-stream rotary, and softmax-routed experts.  The layer code of a family
of configurations (the configuration names the model); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through and an untied
head, like ``deepseek_v3.py``; what differs:

- **Attention.**  ``q [H, D]``, ``k``/``v [Hkv, D]`` from the normed hidden
  state, no biases; ``q`` and ``k`` through an RMSNorm over each head's ``D``;
  rotate-half rotary with ``D/2`` frequencies ``theta^(-2i/D)``, frequency
  ``i`` taking its angle from one of three position streams by
  ``mrope_section`` (a text token has the three equal).  Angles are computed
  from the positions in float32 and the result is **cast back to the
  activations' dtype**: the queries reach the paged kernels in the dtype the
  pool is in.  Scores ``q.k / sqrt(D)``, causal, restricted to the set the
  indexer selects.
- **Indexer.**  ``q^I [Hi, Di]``, one key ``k^I = LayerNorm(x W_kI) [Di]`` and
  head weights ``w [Hi]`` a token; rotary over the whole ``Di`` of both at
  stream 0; ``I(t, s) = sum_j w_j Hi^-1/2 Di^-1/2 relu(q^I_j . k^I_s)`` in
  float32 for ``s <= t``; query ``t`` attends to every ``s <= t`` whose score
  is at or above the ``topk``-th largest of its row (all of them while the
  row is shorter; tokens tied with the cut all kept).  What a token leaves
  in the cache is K, V **and its indexer key**: three sides
  (:meth:`cache_spec`).  Through a cache the selection is the cache
  context's (the Pallas kernels of ``ops/pallas/dsa_attention_kernel.py``
  over the paged pool; a context of ``topk`` tokens or fewer takes the dense
  paged kernels, the same mathematics); a forward with no cache is one
  masked softmax in jnp.
- **Experts**, every layer.  The router is float32 from the hidden state:
  ``p = softmax(x W_r)`` over all experts, the top ``k``, weights
  ``p[chosen] / sum`` (``norm_topk_prob``); no bias, no scale, no shared
  expert.  The layer is told which experts it holds and computes their
  terms only (``held_experts.py``).
- Parameters are created in ``config.dtype``.  Matmul operands are in that
  dtype; the residual stream, the norms, the router, the index scores, the
  softmax statistics and the logits are float32.
- No vision tower: image tokens would enter as ``inputs_embeds`` with
  unequal position streams (``position_ids [3, B, S]``), which the no-cache
  forward accepts.
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
from ..ops.pallas.dsa_attention_kernel import (ATTEND_SCOPE, INDEX_SCOPE,
                                               SELECT_SCOPE)
from ..ops.threshold_search import kth_largest_key, order_keys
from .held_experts import (EMBED_SCOPE, EXPERTS_SCOPE, F32, HEAD_SCOPE,
                           ROUTE_SCOPE, held_experts_forward, _interpret,
                           _Normal, _rms)


@dataclass
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128                   # the router's outputs
    num_experts_per_tok: int = 8
    #: ``(start, stop)`` of the experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.held_experts) if self.held_experts is not None \
            else (0, self.num_experts)


def keye_vl2_tiny(**kw) -> KeyeVL2Config:
    """The CPU tests' preset: every mechanism, toy widths (``topk`` smaller
    than the sequences the tests serve)."""
    for k, v in dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            held_experts=(0, 4), indexer_num_heads=4, indexer_head_dim=8,
            indexer_topk=24, mrope_section=(2, 3, 3),
            max_position_embeddings=256).items():
        kw.setdefault(k, v)
    return KeyeVL2Config(**kw)


def _rotate_half(x, ang):
    """Rotate-half rotary of ``x [B, S, heads, D]`` by ``ang [B, S, D/2]``;
    float32 inside, the input's dtype out."""
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(F32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _angles(pos3, width: int, theta: float, section=None):
    """``[B, S, width/2]`` float32 angles from ``pos3 [3, B, S]``: frequency
    ``i`` reads the stream ``section`` puts it in (stream 0 for all without
    one)."""
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=F32) / width))
    if section is None:
        return pos3[0].astype(F32)[..., None] * inv
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(section),
                        total_repeat_length=width // 2)
    pos = jnp.take(pos3.astype(F32), stream, axis=0)        # [w/2, B, S]
    return jnp.moveaxis(pos, 0, -1) * inv


def _layer_norm(x, g, b, eps):
    x32 = x.astype(F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32)
            + b.astype(F32)).astype(g.dtype)


def indexed_causal_attention(q, k, v, q_idx, k_idx, w, *, topk: int):
    """Attention with no cache: ``q [B, S, H, D]``, ``k``/``v [B, S, Hkv,
    D]``, the indexer's ``q_idx [B, S, Hi, Di]``, ``k_idx [B, S, Di]``,
    ``w [B, S, Hi]`` (scales folded in); one softmax in float32 under
    causal AND selected."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    with jax.named_scope(INDEX_SCOPE):
        s_idx = jnp.einsum("bqjd,bkd->bqjk", q_idx, k_idx,
                           preferred_element_type=F32)
        s_idx = jnp.sum(jnp.maximum(s_idx, 0.0) * w[..., None], axis=2)
    causal = jnp.tril(jnp.ones((S, S), bool))[None]
    with jax.named_scope(SELECT_SCOPE):
        keys = order_keys(jnp.where(causal, s_idx, -jnp.inf))
        cut = kth_largest_key(keys.reshape(B * S, S), topk).reshape(B, S, 1)
        keep = causal & (keys >= cut)                       # [B, S, S]
    with jax.named_scope(ATTEND_SCOPE):
        qg = q.reshape(B, S, Hkv, H // Hkv, D)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                       preferred_element_type=F32) * D ** -0.5
        s = jnp.where(keep[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v,
                       preferred_element_type=F32).astype(q.dtype)
    return o.reshape(B, S, H, D)


class KeyeVL2Attention(Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.c = c
        h, H, Hkv, D = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        Hi, Di = c.indexer_num_heads, c.indexer_head_dim
        init = _Normal(c.initializer_range)

        def mat(*shape):
            return self.create_parameter(list(shape), dtype=c.dtype,
                                         default_initializer=init)

        def vec(n, value):
            return self.create_parameter(
                [n], dtype=c.dtype, default_initializer=I.Constant(value))

        self.q_proj, self.k_proj = mat(h, H * D), mat(h, Hkv * D)
        self.v_proj, self.o_proj = mat(h, Hkv * D), mat(H * D, h)
        self.q_norm, self.k_norm = vec(D, 1.0), vec(D, 1.0)
        self.indexer_q_proj = mat(h, Hi * Di)
        self.indexer_k_proj = mat(h, Di)
        self.indexer_k_norm = vec(Di, 1.0)
        self.indexer_k_norm_bias = vec(Di, 0.0)
        self.indexer_weights_proj = mat(h, Hi)

    def forward(self, x, cache_ctx=None, position_ids=None):
        c = self.c
        B, S, _ = x.shape
        H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        Hi, Di = c.indexer_num_heads, c.indexer_head_dim
        if position_ids is not None:
            pos3 = jnp.asarray(position_ids, jnp.int32)
        else:
            if cache_ctx is None:
                pos = jnp.arange(S, dtype=jnp.int32)[None]
            elif cache_ctx.mode == "prefill":
                pos = cache_ctx.prefill_positions(S)
                pos = jnp.arange(S, dtype=jnp.int32)[None] if pos is None \
                    else pos._value()
            elif cache_ctx.mode == "decode":
                pos = cache_ctx.positions()._value()
            else:
                raise ValueError(f"indexed attention has no "
                                 f"{cache_ctx.mode!r} form")
            pos3 = jnp.broadcast_to(pos[None], (3, B, S))
        q = _rms(jnp.dot(x, self.q_proj._value()).reshape(B, S, H, D),
                 self.q_norm._value(), c.rms_norm_eps)
        k = _rms(jnp.dot(x, self.k_proj._value()).reshape(B, S, Hkv, D),
                 self.k_norm._value(), c.rms_norm_eps)
        v = jnp.dot(x, self.v_proj._value()).reshape(B, S, Hkv, D)
        ang = _angles(pos3, D, c.rope_theta, c.mrope_section)
        q, k = _rotate_half(q, ang), _rotate_half(k, ang)
        with jax.named_scope(INDEX_SCOPE):
            ang_i = _angles(pos3, Di, c.rope_theta)
            q_idx = _rotate_half(
                jnp.dot(x, self.indexer_q_proj._value()
                        ).reshape(B, S, Hi, Di), ang_i)
            k_idx = _rotate_half(_layer_norm(
                jnp.dot(x, self.indexer_k_proj._value()),
                self.indexer_k_norm._value(),
                self.indexer_k_norm_bias._value(), c.rms_norm_eps
            )[:, :, None, :], ang_i)[:, :, 0]
            w = jnp.dot(x, self.indexer_weights_proj._value(),
                        preferred_element_type=F32) * (Hi * Di) ** -0.5
        if cache_ctx is None:
            o = indexed_causal_attention(q, k, v, q_idx, k_idx, w,
                                         topk=c.indexer_topk)
        else:
            q_idx, w = Tensor._wrap(q_idx), Tensor._wrap(w)
            if cache_ctx.mode == "prefill":
                cache_ctx.write_prefill_indexed(
                    Tensor._wrap(k), Tensor._wrap(v), Tensor._wrap(k_idx))
                o = cache_ctx.indexed_prefill_attention(
                    Tensor._wrap(q), q_idx, w, topk=c.indexer_topk)._value()
            else:
                o = cache_ctx.indexed_decode_attention(
                    Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v), q_idx,
                    Tensor._wrap(k_idx), w, topk=c.indexer_topk)._value()
        return jnp.dot(o.reshape(B, S, H * D), self.o_proj._value(),
                       preferred_element_type=F32)


def route(x, w_r, *, top_k: int):
    """``(chosen [T, k], weights [T, k])``: softmax over all experts in
    float32, the top ``k``, weights normalised over the chosen."""
    p = jax.nn.softmax(jnp.dot(x.astype(F32), w_r.astype(F32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, chosen = jax.lax.top_k(p, top_k)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)


class KeyeVL2MoE(Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.c = c
        init = _Normal(c.initializer_range)
        G = c.held[1] - c.held[0]
        h, f = c.hidden_size, c.moe_intermediate_size
        self.gate = self.create_parameter(
            [h, c.num_experts], dtype=c.dtype, default_initializer=init)
        self.experts_gate_up = self.create_parameter(
            [G, h, 2 * f], dtype=c.dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [G, f, h], dtype=c.dtype, default_initializer=init)

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, h = x.shape
        flat = x.reshape(B * S, h)
        live = jnp.ones((B * S,), bool) if cache_ctx is None \
            else cache_ctx.live_tokens(S).reshape(-1)
        with jax.named_scope(ROUTE_SCOPE):
            chosen, weights = route(flat, self.gate._value(),
                                    top_k=c.num_experts_per_tok)
        with jax.named_scope(EXPERTS_SCOPE):
            y, n_held, n_touched = held_experts_forward(
                flat, chosen, weights, live, self.experts_gate_up._value(),
                self.experts_down._value(), held=c.held,
                interpret=_interpret())
        if cache_ctx is not None:
            cache_ctx.note_experts(n_held, n_touched)
        return y.reshape(B, S, h)


class KeyeVL2DecoderLayer(Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.eps = c.rms_norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        self.input_layernorm = gain()
        self.self_attn = KeyeVL2Attention(c)
        self.post_attention_layernorm = gain()
        self.mlp = KeyeVL2MoE(c)

    def forward(self, x, cache_ctx=None, position_ids=None):
        x = x + self.self_attn(
            _rms(x, self.input_layernorm._value(), self.eps), cache_ctx,
            position_ids)
        return x + self.mlp(
            _rms(x, self.post_attention_layernorm._value(), self.eps),
            cache_ctx)


class KeyeVL2Model(Layer):
    def __init__(self, c: KeyeVL2Config):
        super().__init__()
        self.c = c
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([KeyeVL2DecoderLayer(c)
                                 for _ in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))

    def forward(self, input_ids, cache_ctx=None, inputs_embeds=None,
                position_ids=None):
        """``input_ids [B, S]`` (raw), or ``inputs_embeds [B, S, h]`` in their
        place -> final hidden states ``[B, S, h]`` (raw, float32, not yet
        normed)."""
        # the residual stream is float32: every sublayer reads it through a
        # norm and adds a float32 result, so the stream's own rounding does
        # not reach the router and the indexer, whose choices flip on a
        # near tie
        with jax.named_scope(EMBED_SCOPE):
            h = (jnp.take(self.embed_tokens._value(), input_ids, axis=0)
                 if inputs_embeds is None else inputs_embeds).astype(F32)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
            h = layer(h, cache_ctx, position_ids)
        return h


class KeyeVL2ForCausalLM(Layer):
    """The decoder, the final norm and an untied head; logits float32."""

    def __init__(self, config: KeyeVL2Config):
        super().__init__()
        self.config = config
        self.model = KeyeVL2Model(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=_Normal(config.initializer_range))

    def cache_spec(self):
        """Three sides a layer: K and V per KV head, and the indexer's one
        key a token."""
        from ..serving.kv_cache import CacheSpec

        c = self.config
        return CacheSpec.indexed(c.num_hidden_layers, c.num_key_value_heads,
                                 c.head_dim, c.indexer_head_dim,
                                 c.indexer_topk)

    def forward(self, input_ids=None, cache_ctx=None, inputs_embeds=None,
                position_ids=None):
        def raw(a):
            return a._value() if isinstance(a, Tensor) else jnp.asarray(a)

        ids = None if input_ids is None else raw(input_ids).astype(jnp.int32)
        h = self.model(
            ids, cache_ctx,
            None if inputs_embeds is None else raw(inputs_embeds),
            None if position_ids is None else raw(position_ids))
        with jax.named_scope(HEAD_SCOPE):
            if cache_ctx is not None:
                # prefill: the head sees the one row the engine samples from
                h = cache_ctx.select_last(Tensor._wrap(h))._value()
            h = _rms(h, self.model.norm._value(), self.config.rms_norm_eps)
            return Tensor._wrap(jnp.dot(h, self.lm_head._value(),
                                        preferred_element_type=F32))

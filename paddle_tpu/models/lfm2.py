"""LFM2-shaped decoder: **two kinds of operator in one stack** — most layers
mix the sequence with a gated short convolution, every few with grouped-query
attention — and two kinds of feed-forward: leading dense SwiGLU layers, then
sigmoid-routed experts.  The layer code of a family of configurations (the
configuration names the model); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through, like
``mellum.py``; what differs:

- **The convolution operator** (``layer_types[l] == "conv"``): ``[B | C | u]
  = n W_in`` (three ``hidden``-wide parts in that order), ``z = B * u``,
  ``c[t] = sum_k w[k] * z[t - (L - 1) + k]`` (depthwise, causal, one ``L =
  conv_L_cache``-tap filter a channel, no bias, ``z`` zero before position
  0), ``y = (C * c) W_out``.  All a layer remembers of a sequence is the last
  ``L - 1`` columns of ``z``: **a state of fixed size that every token
  rewrites**, not something a token.
- **Attention** (``"full_attention"``): ``q [H, D]``, ``k``/``v [Hkv, D]``
  without biases, an RMSNorm over each head's ``D`` of q and of k, rotate-half
  rotary (plain ``theta``), causal softmax.
- **The cache is stated by layer** (:meth:`Lfm2ForCausalLM.cache_spec`): the
  attention layers keep every token's K and V (the K/V pool's calls:
  ``write_prefill`` / ``prefill_attention`` / ``decode_attention``), the
  convolution layers a state group of ``(L - 1, hidden)`` a slot
  (``shift_state``: the taps of every position, the state read and written
  by the cache).
- **Experts** (layers from ``num_dense_layers`` on): ``deepseek_v3``'s
  ``route`` (sigmoid scores in float32, the top ``k`` of score + bias, weights
  normalised over the chosen — ``+ 1e-6`` here — and scaled), no shared
  expert; told which experts it holds (``held_experts.py``).
- The head is tied to the embedding.  Parameters are created in
  ``config.dtype``; ``z`` is rounded to the cache's dtype before the filter
  (a prefill and a decode step then filter the same numbers); the residual
  stream, the norms, the router, the filter's sum, the softmax statistics and
  the logits are float32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.container import LayerList
from .deepseek_v3 import DeepseekV3MLP, route
from .held_experts import (EMBED_SCOPE, EXPERTS_SCOPE, F32, HEAD_SCOPE,
                           ROUTE_SCOPE, _Normal, _interpret, _rms,
                           held_experts_forward)
from .mellum import _rotary, windowed_causal_attention

CONV, ATTENTION = "conv", "full_attention"

#: named scope of a convolution operator's own work in a compiled program's
#: op names: the gates and the filter (its two projections are ``Layer``
#: calls, the state's write is the cache's ``state.write``)
CONV_MIX_SCOPE = "conv.mix"


@dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    intermediate_size: int = 11776           # the leading dense layers'
    num_dense_layers: int = 2
    moe_intermediate_size: int = 1536
    num_experts: int = 64                    # the router's outputs
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    #: ``(start, stop)`` of the experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    #: a kind a layer; None = conv, conv, then (attention, conv, conv, conv)
    layer_types: Optional[Tuple[str, ...]] = None
    conv_L_cache: int = 3                    # the filter's taps
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.held_experts) if self.held_experts is not None \
            else (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = tuple(self.layer_types) if self.layer_types is not None \
            else tuple(ATTENTION if i % 4 == 2 else CONV
                       for i in range(self.num_hidden_layers))
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {kinds} is not a kind "
                             f"({CONV} | {ATTENTION}) for each of "
                             f"{self.num_hidden_layers} layers")
        return kinds


def lfm2_tiny(**kw) -> Lfm2Config:
    """The CPU tests' preset: every mechanism, toy widths (one dense layer,
    then experts of which a quarter is held; attention at layer 2 of 4)."""
    for k, v in dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=96, num_dense_layers=1,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            held_experts=(0, 4), max_position_embeddings=256).items():
        kw.setdefault(k, v)
    return Lfm2Config(**kw)


class _Linear(Layer):
    """``x W`` in float32: a ``Layer``, so that a compiled program names the
    matmul by where it stands (``.../conv/in_proj``)."""

    def __init__(self, c: Lfm2Config, n_in: int, n_out: int):
        super().__init__()
        self.weight = self.create_parameter(
            [n_in, n_out], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))

    def forward(self, x):
        return jnp.dot(x, self.weight._value(), preferred_element_type=F32)


class Lfm2ShortConv(Layer):
    """The gated short convolution.  ``filter [L, hidden]``: tap ``k``
    multiplies ``z[t - (L - 1) + k]``."""

    def __init__(self, c: Lfm2Config):
        super().__init__()
        self.taps = c.conv_L_cache
        self.in_proj = _Linear(c, c.hidden_size, 3 * c.hidden_size)
        self.out_proj = _Linear(c, c.hidden_size, c.hidden_size)
        self.filter = self.create_parameter(
            [c.conv_L_cache, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))

    def forward(self, x, cache_ctx=None):
        h = x.shape[-1]
        bcu = self.in_proj(x)                           # [B, S, 3h] float32
        with jax.named_scope(CONV_MIX_SCOPE):
            z = (bcu[..., :h] * bcu[..., 2 * h:]).astype(x.dtype)
        if cache_ctx is None:
            ext = jnp.pad(z, ((0, 0), (self.taps - 1, 0), (0, 0)))
            taps = [ext[:, k:k + x.shape[1]] for k in range(self.taps)]
        else:
            # the cache hands back every position's taps: the columns before
            # the call's first come from the group's state
            taps = cache_ctx.shift_state(z)
        with jax.named_scope(CONV_MIX_SCOPE):
            w = self.filter._value().astype(F32)
            c = sum(w[k] * taps[k].astype(F32) for k in range(self.taps))
            y = (bcu[..., h:2 * h] * c).astype(x.dtype)
        return self.out_proj(y)


class Lfm2Attention(Layer):
    def __init__(self, c: Lfm2Config):
        super().__init__()
        self.c = c
        D = c.head_dim
        self.inv_freq = np.asarray(1.0 / c.rope_theta ** (
            np.arange(0, D, 2, dtype=np.float64) / D), np.float32)
        h, H, Hkv = c.hidden_size, c.num_attention_heads, \
            c.num_key_value_heads
        init = _Normal(c.initializer_range)

        def mat(*shape):
            return self.create_parameter(list(shape), dtype=c.dtype,
                                         default_initializer=init)

        def vec(n):
            return self.create_parameter(
                [n], dtype=c.dtype, default_initializer=I.Constant(1.0))

        # q, k and v output-major ``[heads * D, h]`` (``evabyte.py`` has why)
        self.q_proj, self.k_proj = mat(H * D, h), mat(Hkv * D, h)
        self.v_proj, self.out_proj = mat(Hkv * D, h), mat(H * D, h)
        self.q_layernorm, self.k_layernorm = vec(D), vec(D)

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, _ = x.shape
        H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        if cache_ctx is None:
            pos = jnp.arange(S, dtype=jnp.int32)[None]
        elif cache_ctx.mode == "prefill":
            pos = cache_ctx.prefill_positions(S)
            pos = jnp.arange(S, dtype=jnp.int32)[None] if pos is None \
                else pos._value()
        elif cache_ctx.mode == "decode":
            pos = cache_ctx.positions()._value()
        else:
            raise ValueError(f"a stack with state layers has no "
                             f"{cache_ctx.mode!r} form")
        pos = jnp.broadcast_to(pos, (B, S))

        def proj(w, heads):
            return jnp.einsum("bsh,nh->bsn", x, w._value()
                              ).reshape(B, S, heads, D)

        q = _rms(proj(self.q_proj, H), self.q_layernorm._value(), c.norm_eps)
        k = _rms(proj(self.k_proj, Hkv), self.k_layernorm._value(),
                 c.norm_eps)
        v = proj(self.v_proj, Hkv)
        q = _rotary(q, pos, self.inv_freq, 1.0)
        k = _rotary(k, pos, self.inv_freq, 1.0)
        if cache_ctx is None:
            o = windowed_causal_attention(q, k, v, window=0)
        elif cache_ctx.mode == "prefill":
            cache_ctx.write_prefill(Tensor._wrap(k), Tensor._wrap(v))
            o = cache_ctx.prefill_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v))._value()
        else:
            o = cache_ctx.decode_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v))._value()
        return jnp.dot(o.reshape(B, S, H * D).astype(x.dtype),
                       self.out_proj._value(), preferred_element_type=F32)


class Lfm2MoE(Layer):
    def __init__(self, c: Lfm2Config):
        super().__init__()
        self.c = c
        init = _Normal(c.initializer_range)
        G = c.held[1] - c.held[0]
        h, f = c.hidden_size, c.moe_intermediate_size
        self.gate = self.create_parameter(
            [h, c.num_experts], dtype=c.dtype, default_initializer=init)
        # the selection bias: used for the choice, never for the weights
        self.register_buffer("expert_bias", Tensor._wrap(
            jnp.zeros((c.num_experts,), F32)))
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
            chosen, weights = route(
                flat, self.gate._value(), self.expert_bias._value(),
                top_k=c.num_experts_per_tok, scale=c.routed_scaling_factor,
                eps=1e-6)
        with jax.named_scope(EXPERTS_SCOPE):
            y, n_held, n_touched = held_experts_forward(
                flat, chosen, weights, live, self.experts_gate_up._value(),
                self.experts_down._value(), held=c.held,
                interpret=_interpret())
        if cache_ctx is not None:
            cache_ctx.note_experts(n_held, n_touched)
        return y.reshape(B, S, h)


class Lfm2DecoderLayer(Layer):
    def __init__(self, c: Lfm2Config, index: int, kind: str):
        super().__init__()
        self.eps = c.norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        self.operator_norm = gain()
        # the operator under the name its kind has in the published model
        self.is_conv = kind == CONV
        if self.is_conv:
            self.conv = Lfm2ShortConv(c)
        else:
            self.self_attn = Lfm2Attention(c)
        self.ffn_norm = gain()
        self.is_moe = index >= c.num_dense_layers
        self.feed_forward = Lfm2MoE(c) if self.is_moe \
            else DeepseekV3MLP(c, c.intermediate_size)

    def forward(self, x, cache_ctx=None):
        op = self.conv if self.is_conv else self.self_attn
        x = x + op(_rms(x, self.operator_norm._value(), self.eps), cache_ctx)
        m = _rms(x, self.ffn_norm._value(), self.eps)
        return x + (self.feed_forward(m, cache_ctx) if self.is_moe
                    else self.feed_forward(m))


class Lfm2Model(Layer):
    def __init__(self, c: Lfm2Config):
        super().__init__()
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([Lfm2DecoderLayer(c, i, kind)
                                 for i, kind in enumerate(c.kinds)])
        self.embedding_norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))

    def forward(self, input_ids, cache_ctx=None):
        """``input_ids [B, S]`` (raw) -> final hidden states ``[B, S, h]``
        (raw, float32, not yet normed)."""
        with jax.named_scope(EMBED_SCOPE):
            h = jnp.take(self.embed_tokens._value(), input_ids, axis=0
                         ).astype(F32)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
            h = layer(h, cache_ctx)
        return h


class Lfm2ForCausalLM(Layer):
    """The decoder, the final norm and the head tied to the embedding;
    logits float32."""

    def __init__(self, config: Lfm2Config):
        super().__init__()
        self.config = config
        self.model = Lfm2Model(config)

    def cache_spec(self):
        """Stated by layer: the attention layers keep K and V per KV head of
        every token, the convolution layers the last ``conv_L_cache - 1``
        columns of their gated product — a state a slot, nothing a token.
        The attention group is named even where it has no layer: it counts
        the sequence's positions in blocks."""
        from ..serving.kv_cache import CacheGroup, CacheSpec

        c = self.config
        by_kind = {kind: tuple(i for i, k in enumerate(c.kinds) if k == kind)
                   for kind in (ATTENTION, CONV)}
        groups = [CacheGroup(by_kind[ATTENTION],
                             ((c.num_key_value_heads, c.head_dim),) * 2)]
        if by_kind[CONV]:
            groups.append(CacheGroup(
                by_kind[CONV], ((c.conv_L_cache - 1, c.hidden_size),),
                state=True))
        return CacheSpec.by_layer(groups)

    def forward(self, input_ids, cache_ctx=None):
        ids = (input_ids._value() if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids)).astype(jnp.int32)
        h = self.model(ids, cache_ctx)
        with jax.named_scope(HEAD_SCOPE):
            if cache_ctx is not None:
                # prefill: the head sees the one row the engine samples from
                h = cache_ctx.select_last(Tensor._wrap(h))._value()
            h = _rms(h, self.model.embedding_norm._value(),
                     self.config.norm_eps)
            return Tensor._wrap(jnp.einsum(
                "bsh,vh->bsv", h, self.model.embed_tokens._value(),
                preferred_element_type=F32))

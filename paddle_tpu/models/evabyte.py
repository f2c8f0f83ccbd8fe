"""EvaByte-shaped decoder: a byte-level model whose attention (EVA) keeps the
exact keys and values of the query's own **window** and, of every window
passed, one learned **summary** key and value a chunk.  The layer code of a
family of configurations (the configuration names the model); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through and an untied
head, like ``keye_vl2.py``; what differs:

- **Norm** with a unit offset: ``u = x / sqrt(mean(x^2) + eps) * (1 + g)``.
- **Attention.**  ``q``, ``k``, ``v [H, D]`` from the normed hidden state, no
  biases; rotate-half rotary over the whole ``D`` (``keye_vl2``'s, on one
  position stream: float32 angles, the activations' dtype out).  Position
  ``i`` lies in window ``i // W``.  The **summary** of a chunk ``c`` of ``C``
  positions, by a head's learned ``phi``, ``mu [D]``: ``a_j = softmax_{j in
  c}(s <k_j, phi>)``, ``k~ = sum_j a_j k_j + mu``, ``v~ = sum_j a_j v_j``
  (of rotated keys, float32).  A query takes ONE softmax over the exact keys
  ``j <= i`` of its own window (logits ``s <q, k_j>``) and the summaries of
  every chunk of every earlier window (logits ``s <q, k~>``, no other term);
  ``s = D^-1/2``.  Through a cache that is the cache context's two calls
  (``serving/window_cache.py``: the Pallas kernels of
  ``ops/pallas/eva_attention_kernel.py`` over the exact and the summary
  group; a slot inside its first window has no summary item); a forward with
  no cache is one masked softmax in jnp.
- **SwiGLU** ``W_down(silu(W_gate u) * W_up u)``, no bias.
- **Head** ``[h, P x V]``, head-major: ``P`` next-byte predictors.  Head 0 is
  the next byte, which is what is served; ``all_heads=True`` returns the
  ``P``.  Drafting with the other heads (multibyte self-speculation) is not
  built.
- Parameters are created in ``config.dtype``.  Matmul operands are in that
  dtype; the residual stream, the norms, the summaries' arithmetic, the
  softmax statistics and the logits are float32.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.container import LayerList
from ..ops.pallas.eva_attention_kernel import (ATTEND_SCOPE, NEG_INF,
                                               chunk_summaries)
from .held_experts import EMBED_SCOPE, F32, HEAD_SCOPE, _Normal
from .keye_vl2 import _angles, _rotate_half


@dataclass
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e5
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def evabyte_tiny(**kw) -> EvaByteConfig:
    """The CPU tests' preset: every mechanism, toy widths (windows far
    shorter than the sequences the tests serve)."""
    for k, v in dict(
            vocab_size=64, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=96, window_size=32, chunk_size=4,
            num_pred_heads=3, max_position_embeddings=256).items():
        kw.setdefault(k, v)
    return EvaByteConfig(**kw)


def _rms_unit(x, g, eps):
    """RMSNorm with a unit offset in float32; the result in the gain's dtype
    (the dtype the next matmul's weights are in)."""
    x32 = x.astype(F32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * (1.0 + g.astype(F32))).astype(g.dtype)


def eva_causal_attention(q, k, v, phi, mu, *, window: int, chunk: int):
    """Attention with no cache: ``q``/``k``/``v [B, S, H, D]`` (rotated),
    ``phi``/``mu [H, D]``; one softmax in float32 over the exact keys of each
    query's own window and the summaries of the windows before it."""
    B, S, H, D = q.shape
    scale = D ** -0.5
    T = S // window * window                   # the whole windows
    ks, vs = chunk_summaries(k[:, :T], v[:, :T], phi, mu, chunk=chunk,
                             scale=scale)
    with jax.named_scope(ATTEND_SCOPE):
        pos = jnp.arange(S, dtype=jnp.int32)
        win = pos // window
        ok_ex = (win[:, None] == win[None, :]) & (pos[None, :] <= pos[:, None])
        c_win = jnp.arange(T // chunk, dtype=jnp.int32) * chunk // window
        ok_su = c_win[None, :] < win[:, None]
        q32 = q.astype(F32)
        s = jnp.concatenate([
            jnp.where(ok_su[None, None],
                      jnp.einsum("bqhd,bkhd->bhqk", q32, ks), NEG_INF),
            jnp.where(ok_ex[None, None],
                      jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(F32)),
                      NEG_INF)], axis=-1) * scale
        p = jax.nn.softmax(s, axis=-1)
        n = T // chunk
        o = (jnp.einsum("bhqk,bkhd->bqhd", p[..., :n], vs)
             + jnp.einsum("bhqk,bkhd->bqhd", p[..., n:], v.astype(F32)))
    return o.astype(q.dtype)


class EvaByteAttention(Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        self.c = c
        h, H, D = c.hidden_size, c.num_attention_heads, c.head_dim
        init = _Normal(c.initializer_range)

        def mat(*shape, init=init):
            return self.create_parameter(list(shape), dtype=c.dtype,
                                         default_initializer=init)

        # q, k and v are kept output-major ``[H * D, h]``: the form XLA:TPU
        # gives the projections' weights anyway (heads on the rows, for the
        # rotary that follows), by a copy of each in every program when they
        # are stored the other way
        self.q_proj, self.k_proj = mat(H * D, h), mat(H * D, h)
        self.v_proj, self.o_proj = mat(H * D, h), mat(H * D, h)
        #: the pooling query and the pooled key's offset, a head
        self.summary_phi = mat(H, D, init=_Normal(D ** -0.5))
        self.summary_mu = mat(H, D, init=_Normal(D ** -0.5))

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, _ = x.shape
        H, D = c.num_attention_heads, c.head_dim
        if cache_ctx is None:
            pos = jnp.arange(S, dtype=jnp.int32)[None]
        elif cache_ctx.mode == "prefill":
            pos = cache_ctx.prefill_positions(S)
            pos = jnp.arange(S, dtype=jnp.int32)[None] if pos is None \
                else pos._value()
        elif cache_ctx.mode == "decode":
            pos = cache_ctx.positions()._value()
        else:
            raise ValueError(f"windowed attention has no "
                             f"{cache_ctx.mode!r} form")
        q, k, v = (jnp.einsum("bsh,nh->bsn", x, w._value()
                              ).reshape(B, S, H, D)
                   for w in (self.q_proj, self.k_proj, self.v_proj))
        ang = _angles(jnp.broadcast_to(pos, (B, S))[None], D, c.rope_theta)
        q, k = _rotate_half(q, ang), _rotate_half(k, ang)
        phi, mu = self.summary_phi._value(), self.summary_mu._value()
        if cache_ctx is None:
            o = eva_causal_attention(q, k, v, phi, mu, window=c.window_size,
                                     chunk=c.chunk_size)
        elif cache_ctx.mode == "prefill":
            o = cache_ctx.windowed_prefill_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v), phi,
                mu)._value()
        else:
            o = cache_ctx.windowed_decode_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v))._value()
        return jnp.dot(o.reshape(B, S, H * D), self.o_proj._value(),
                       preferred_element_type=F32)


class EvaByteMLP(Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        init = _Normal(c.initializer_range)
        h, f = c.hidden_size, c.intermediate_size
        self.gate_proj, self.up_proj, self.down_proj = (
            self.create_parameter(list(shape), dtype=c.dtype,
                                  default_initializer=init)
            for shape in ((h, f), (h, f), (f, h)))

    def forward(self, x):
        gate = jnp.dot(x, self.gate_proj._value(), preferred_element_type=F32)
        up = jnp.dot(x, self.up_proj._value(), preferred_element_type=F32)
        return jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype),
                       self.down_proj._value(), preferred_element_type=F32)


class EvaByteDecoderLayer(Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        self.eps = c.rms_norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(0.0))

        self.input_layernorm = gain()
        self.self_attn = EvaByteAttention(c)
        self.post_attention_layernorm = gain()
        self.mlp = EvaByteMLP(c)

    def forward(self, x, cache_ctx=None):
        x = x + self.self_attn(
            _rms_unit(x, self.input_layernorm._value(), self.eps), cache_ctx)
        return x + self.mlp(
            _rms_unit(x, self.post_attention_layernorm._value(), self.eps))


class EvaByteModel(Layer):
    def __init__(self, c: EvaByteConfig):
        super().__init__()
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([EvaByteDecoderLayer(c)
                                 for _ in range(c.num_hidden_layers)])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(0.0))

    def forward(self, input_ids, cache_ctx=None):
        """``input_ids [B, S]`` (raw) -> final hidden states ``[B, S, h]``
        (raw, float32, not yet normed): the residual stream is float32."""
        with jax.named_scope(EMBED_SCOPE):
            h = jnp.take(self.embed_tokens._value(), input_ids, axis=0
                         ).astype(F32)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
            h = layer(h, cache_ctx)
        return h


class EvaByteForCausalLM(Layer):
    """The decoder, the final norm and an untied head of ``num_pred_heads``
    predictors; logits float32."""

    def __init__(self, config: EvaByteConfig):
        super().__init__()
        self.config = config
        self.model = EvaByteModel(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.num_pred_heads * config.vocab_size],
            dtype=config.dtype,
            default_initializer=_Normal(config.initializer_range))

    def cache_spec(self):
        """Two groups: exact K and V per head inside the current window, and
        one summary key and value a chunk of every window passed."""
        from ..serving.kv_cache import CacheSpec

        c = self.config
        return CacheSpec.windowed(c.num_hidden_layers, c.num_key_value_heads,
                                  c.head_dim, c.window_size, c.chunk_size)

    def summary_params(self):
        """``[(phi, mu)]`` a layer: what the engine's publishing program
        pools a closed window's keys and values with."""
        return [(layer.self_attn.summary_phi, layer.self_attn.summary_mu)
                for layer in self.model.layers]

    def forward(self, input_ids, cache_ctx=None, all_heads: bool = False):
        """Logits of the next byte ``[B, S, V]``; ``all_heads``: of every
        predictor, ``[B, S, P, V]`` (predictor ``p`` is ``p + 1`` bytes
        ahead)."""
        c = self.config
        ids = (input_ids._value() if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids)).astype(jnp.int32)
        h = self.model(ids, cache_ctx)
        with jax.named_scope(HEAD_SCOPE):
            if cache_ctx is not None:
                # prefill: the head sees the one row the engine samples from
                h = cache_ctx.select_last(Tensor._wrap(h))._value()
            h = _rms_unit(h, self.model.norm._value(), c.rms_norm_eps)
            head = self.lm_head._value()
            if not all_heads:
                head = head[:, :c.vocab_size]            # predictor 0
            logits = jnp.dot(h, head, preferred_element_type=F32)
            if all_heads:
                logits = logits.reshape(*logits.shape[:2], c.num_pred_heads,
                                        c.vocab_size)
            return Tensor._wrap(logits)

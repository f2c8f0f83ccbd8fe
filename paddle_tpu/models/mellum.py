"""Mellum-2-shaped decoder: grouped-query attention whose layers are of **two
kinds in one stack** — most attend over a sliding window behind the query, every
few over the whole sequence under a YaRN-scaled rotary — and softmax-routed
experts in every layer.  The layer code of a family of configurations (the
configuration names the model); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through and an untied
head, like ``keye_vl2.py``; what differs:

- **Attention.**  ``q [H, D]``, ``k``/``v [Hkv, D]`` from the normed hidden
  state, no biases; ``q`` and ``k`` through an RMSNorm over each head's ``D``,
  then rotate-half rotary.  ``layer_types`` gives each layer its kind:
  a ``sliding_attention`` layer's query at position ``i`` attends keys ``j``
  with ``i - sliding_window < j <= i`` (its own position counts) under the
  plain rotary (``theta^(-2i/D)``); a ``full_attention`` layer attends every
  ``j <= i`` under **YaRN**: each pair of dimensions blends its frequency with
  the frequency ``/ factor`` by a linear ramp between the two correction
  dimensions (``beta_fast``, ``beta_slow`` rotations over the original
  length), and cos and sin are multiplied by ``attention_factor``.  Two
  static tables in one model, chosen by the layer's kind.
- **The cache is stated by layer** (:meth:`MellumForCausalLM.cache_spec`): the
  full layers keep every token's K and V, the sliding layers only the last
  ``sliding_window`` — two groups, each a pool of its own.  The attention
  calls are the K/V pool's (``write_prefill`` / ``prefill_attention`` /
  ``decode_attention``); which keys a call reads is its layer's group's.
- **Experts**, every layer: ``keye_vl2``'s layer as it is (softmax over all
  experts in float32, the top ``k`` renormalised, no bias, no scale, no shared
  expert; told which experts it holds, ``held_experts.py``).
- Parameters are created in ``config.dtype``; q, k and v projections are
  stored output-major (heads on the rows, the form XLA:TPU gives them for the
  rotary that follows).  The residual stream, the norms, the router, the
  softmax statistics and the logits are float32.
- No MTP head: the published config has no key that sizes one.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.container import LayerList
from .held_experts import EMBED_SCOPE, F32, HEAD_SCOPE, _Normal, _rms
from .keye_vl2 import KeyeVL2MoE

SLIDING, FULL = "sliding_attention", "full_attention"

#: named scope of a layer's attention with no cache (through a cache the
#: paged kernels carry their own names)
ATTEND_SCOPE = "swa.attend"


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64                    # the router's outputs
    num_experts_per_tok: int = 8
    #: ``(start, stop)`` of the experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    #: a kind a layer; None = three sliding layers to one full layer
    layer_types: Optional[Tuple[str, ...]] = None
    sliding_window: int = 1024
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 5e5
    #: the full layers' YaRN (``rope_parameters.full_attention``)
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    #: None = ``0.1 ln(factor) + 1``
    yarn_attention_factor: Optional[float] = None
    initializer_range: float = 0.02
    dtype: str = "float32"

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.held_experts) if self.held_experts is not None \
            else (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        kinds = tuple(self.layer_types) if self.layer_types is not None \
            else tuple(FULL if i % 4 == 3 else SLIDING
                       for i in range(self.num_hidden_layers))
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {kinds} is not a kind "
                             f"({SLIDING} | {FULL}) for each of "
                             f"{self.num_hidden_layers} layers")
        return kinds


def mellum_tiny(**kw) -> MellumConfig:
    """The CPU tests' preset: every mechanism, toy widths (a window far
    shorter than the sequences the tests serve, YaRN's original length
    shorter than them too)."""
    for k, v in dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
            held_experts=(0, 4), sliding_window=24,
            max_position_embeddings=256, yarn_factor=4.0,
            yarn_original_max_position_embeddings=64).items():
        kw.setdefault(k, v)
    return MellumConfig(**kw)


def rotary_table(c: MellumConfig, kind: str):
    """``(inv_freq [D/2] float32, factor)`` of a layer of ``kind``: the plain
    frequencies and 1, or YaRN's blended ones and its attention factor."""
    D = c.head_dim
    freqs = c.rope_theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    if kind == SLIDING:
        return np.asarray(1.0 / freqs, np.float32), 1.0
    factor, orig = float(c.yarn_factor), c.yarn_original_max_position_embeddings

    def correction_dim(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(c.rope_theta))

    low = max(math.floor(correction_dim(c.yarn_beta_fast)), 0)   # truncate
    high = min(math.ceil(correction_dim(c.yarn_beta_slow)), D - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(D // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # ramp 0: the frequency as it is; ramp 1: the frequency / factor
    inv = (1.0 / freqs) * (1.0 - ramp) + (1.0 / (factor * freqs)) * ramp
    att = c.yarn_attention_factor if c.yarn_attention_factor is not None \
        else 0.1 * math.log(factor) + 1.0
    return np.asarray(inv, np.float32), float(att)


def _rotary(x, pos, inv_freq, factor: float):
    """Rotate-half rotary of ``x [B, S, heads, D]`` at ``pos [B, S]``; cos
    and sin times ``factor``; float32 inside, the input's dtype out."""
    ang = pos.astype(F32)[..., None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    x32 = x.astype(F32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def windowed_causal_attention(q, k, v, *, window: int):
    """Attention with no cache: ``q [B, S, H, D]``, ``k``/``v [B, S, Hkv,
    D]``; one softmax in float32 under ``j <= i`` and, with a ``window``,
    ``j > i - window``."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    with jax.named_scope(ATTEND_SCOPE):
        pos = jnp.arange(S, dtype=jnp.int32)
        keep = pos[None, :] <= pos[:, None]
        if window:
            keep &= pos[None, :] > pos[:, None] - window
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q.reshape(B, S, Hkv, H // Hkv, D),
                       k, preferred_element_type=F32) * D ** -0.5
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1).astype(q.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v,
                       preferred_element_type=F32).astype(q.dtype)
    return o.reshape(B, S, H, D)


class MellumAttention(Layer):
    def __init__(self, c: MellumConfig, kind: str):
        super().__init__()
        self.c, self.kind = c, kind
        self.window = c.sliding_window if kind == SLIDING else 0
        self.inv_freq, self.rotary_factor = rotary_table(c, kind)
        h, H, Hkv, D = (c.hidden_size, c.num_attention_heads,
                        c.num_key_value_heads, c.head_dim)
        init = _Normal(c.initializer_range)

        def mat(*shape):
            return self.create_parameter(list(shape), dtype=c.dtype,
                                         default_initializer=init)

        def vec(n):
            return self.create_parameter(
                [n], dtype=c.dtype, default_initializer=I.Constant(1.0))

        # q, k and v output-major ``[heads * D, h]`` (``evabyte.py`` has why)
        self.q_proj, self.k_proj = mat(H * D, h), mat(Hkv * D, h)
        self.v_proj, self.o_proj = mat(Hkv * D, h), mat(H * D, h)
        self.q_norm, self.k_norm = vec(D), vec(D)

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
            raise ValueError(f"attention by kind of layer has no "
                             f"{cache_ctx.mode!r} form")
        pos = jnp.broadcast_to(pos, (B, S))

        def proj(w, heads):
            return jnp.einsum("bsh,nh->bsn", x, w._value()
                              ).reshape(B, S, heads, D)

        q = _rms(proj(self.q_proj, H), self.q_norm._value(), c.rms_norm_eps)
        k = _rms(proj(self.k_proj, Hkv), self.k_norm._value(), c.rms_norm_eps)
        v = proj(self.v_proj, Hkv)
        q = _rotary(q, pos, self.inv_freq, self.rotary_factor)
        k = _rotary(k, pos, self.inv_freq, self.rotary_factor)
        if cache_ctx is None:
            o = windowed_causal_attention(q, k, v, window=self.window)
        elif cache_ctx.mode == "prefill":
            # the layer's group masks by its window and holds the blocks
            cache_ctx.write_prefill(Tensor._wrap(k), Tensor._wrap(v))
            o = cache_ctx.prefill_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v))._value()
        else:
            o = cache_ctx.decode_attention(
                Tensor._wrap(q), Tensor._wrap(k), Tensor._wrap(v))._value()
        return jnp.dot(o.reshape(B, S, H * D).astype(x.dtype),
                       self.o_proj._value(), preferred_element_type=F32)


class MellumDecoderLayer(Layer):
    def __init__(self, c: MellumConfig, kind: str):
        super().__init__()
        self.eps = c.rms_norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        self.input_layernorm = gain()
        self.self_attn = MellumAttention(c, kind)
        self.post_attention_layernorm = gain()
        self.mlp = KeyeVL2MoE(c)

    def forward(self, x, cache_ctx=None):
        x = x + self.self_attn(
            _rms(x, self.input_layernorm._value(), self.eps), cache_ctx)
        return x + self.mlp(
            _rms(x, self.post_attention_layernorm._value(), self.eps),
            cache_ctx)


class MellumModel(Layer):
    def __init__(self, c: MellumConfig):
        super().__init__()
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([MellumDecoderLayer(c, kind)
                                 for kind in c.kinds])
        self.norm = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))

    def forward(self, input_ids, cache_ctx=None):
        """``input_ids [B, S]`` (raw) -> final hidden states ``[B, S, h]``
        (raw, float32, not yet normed): the residual stream is float32, so
        its own rounding does not reach the router, whose choices flip on a
        near tie."""
        with jax.named_scope(EMBED_SCOPE):
            h = jnp.take(self.embed_tokens._value(), input_ids, axis=0
                         ).astype(F32)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
            h = layer(h, cache_ctx)
        return h


class MellumForCausalLM(Layer):
    """The decoder, the final norm and an untied head; logits float32."""

    def __init__(self, config: MellumConfig):
        super().__init__()
        self.config = config
        self.model = MellumModel(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=_Normal(config.initializer_range))

    def cache_spec(self):
        """K and V per KV head in every layer, stated by layer: the full
        layers keep every token, the sliding layers the last
        ``sliding_window``."""
        from ..serving.kv_cache import CacheGroup, CacheSpec

        c = self.config
        sides = ((c.num_key_value_heads, c.head_dim),) * 2
        by_kind = {kind: tuple(i for i, k in enumerate(c.kinds) if k == kind)
                   for kind in (FULL, SLIDING)}
        groups = [CacheGroup(by_kind[FULL], sides),
                  CacheGroup(by_kind[SLIDING], sides, c.sliding_window)]
        return CacheSpec.by_layer([g for g in groups if g.layers])

    def forward(self, input_ids, cache_ctx=None):
        ids = (input_ids._value() if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids)).astype(jnp.int32)
        h = self.model(ids, cache_ctx)
        with jax.named_scope(HEAD_SCOPE):
            if cache_ctx is not None:
                # prefill: the head sees the one row the engine samples from
                h = cache_ctx.select_last(Tensor._wrap(h))._value()
            h = _rms(h, self.model.norm._value(), self.config.rms_norm_eps)
            return Tensor._wrap(jnp.dot(h, self.lm_head._value(),
                                        preferred_element_type=F32))

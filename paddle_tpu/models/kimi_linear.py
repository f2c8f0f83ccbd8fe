"""Kimi-Linear-shaped decoder: **gated delta-rule linear attention** (KDA) in
most layers, latent attention without positions (MLA, NoPE) in every fourth,
a leading dense SwiGLU and then sigmoid-routed experts with a shared one.  The
layer code of a family of configurations (the configuration names the model;
*Kimi Linear*, arXiv:2510.26692); serving only.

Pre-RMSNorm residual blocks with ``cache_ctx`` threaded through and an untied
head, like ``deepseek_v3.py``, whose latent attention
(``DeepseekV3Attention`` with ``q_lora_rank=None`` and ``mla_use_nope``),
dense and shared SwiGLU, router and held experts (``DeepseekV3MoE``) this
module uses as they are.  What it adds is **the KDA operator**
(``linear_attn_config``: ``H = num_heads`` heads of ``D = head_dim``, a
depthwise causal convolution of ``short_conv_kernel_size`` taps).  A token
``t``, ``x`` the normed hidden state:

- ``q~ = x W_q``, ``k~ = x W_k``, ``v~ = x W_v`` (``hidden -> H D`` each);
  each through its convolution ``c[t] = sum_j w[j] * z[t - (L - 1) + j]``
  (zeros before position 0, no bias) and SiLU; per head ``q = l2norm(q') *
  D^-1/2``, ``k = l2norm(k')``, ``v = v'`` (``l2norm(a) = a / sqrt(sum a^2 +
  1e-6)``).
- the forget gate, a channel: ``g = -exp(A_log[h]) * softplus(f_b(f_a(x)) +
  dt_bias)`` (``f_a: hidden -> D``, ``f_b: D -> H D``, no biases), ``alpha =
  exp(g)`` in (0, 1); the write strength ``beta = sigmoid(x W_beta)``, one a
  head.
- the recurrence on ``S [D, D]`` a head, zeros before position 0: ``S' =
  Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
  ``o_t = S_t^T q_t``.
- the output: ``y = W_o [ rmsnorm_head(o_t) * sigmoid(g_b(g_a(x))) ]``
  (``g_a: hidden -> D``, ``g_b: D -> H D``, no biases; the norm over each
  head's ``D`` with one learned gain of ``D``).

All a KDA layer remembers of a sequence is ``S`` (float32, 2 MB a layer at
the published widths) and the last ``L - 1`` columns of ``[q~ | k~ | v~]``:
**the cache it states** (:meth:`KimiLinearForCausalLM.cache_spec`) is a latent
group for the attention layers and a state group with two sides for the KDA
layers — a shift side the pool shifts and a recurrent side the pool only
stores: a tail prefill asks the cache context for the state its slot starts
from and the ends to return states at, runs the chunked scan
(``ops/pallas/kda_kernel.py::kda_chunk_prefill``) and hands the states back;
a decode step hands the context a function of the layer's state buffer
(``kda_decode_step``, the running slots' state rewritten in place).  No
token-by-token scan on any path; a forward with no cache runs the chunked
scan from zeros.

**The snapshot stride is 2,048** (``snapshot_stride``): a snapshot of this
group is every KDA layer's ``S`` and columns, 21.7 MB at ten layers — what
the three latent layers keep of 6.3k tokens — so the pool leaves one where a
resident piece ends and not every 256 tokens as a state of kilobytes can.

**Precision.**  Parameters in ``config.dtype``; the residual stream, the
norms, the router, ``g``, ``alpha``, ``beta``, the l2 norms, **the state and
the whole recurrence with its operands**, softmax statistics and logits
float32: ``S`` accumulates over tens of thousands of tokens and a bfloat16
state drifts.  ``[q~ | k~ | v~]`` is rounded to the cache's dtype before the
filter, so a prefill and a decode step filter the same numbers.

**Assumed** (the published ``config.json`` has no key for them; the family's
published modelling code is the source): no bias on any projection, ``f_b``
and ``g_b`` included; SiLU after the convolution; the gate's form above;
``A_log`` one a head and ``dt_bias`` one a channel; the l2 norm's ``1e-6``.
Departures: the experts a chip holds (``held_experts``), and no multi-token
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
from ..ops.pallas.kda_kernel import (CHUNK, MIX_SCOPE, kda_chunk_prefill,
                                     kda_decode_step)
from .deepseek_v3 import DeepseekV3Attention, DeepseekV3MLP, DeepseekV3MoE
from .held_experts import (EMBED_SCOPE, F32, HEAD_SCOPE, _interpret, _Normal,
                           _rms)
from .lfm2 import _Linear

KDA, MLA = "kda", "mla"


@dataclass
class KimiLinearConfig:
    """The widths under the names ``DeepseekV3Config`` gives them where the
    two families share a layer (the adapter maps the published keys)."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    #: latent attention (``q_lora_rank`` None: one query projection; NoPE)
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    #: KDA (``linear_attn_config``)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    #: layers (0-indexed) with latent attention; None: every fourth and the
    #: last (the published ``full_attn_layers``, 1-indexed there)
    full_attn_layers: Optional[Tuple[int, ...]] = None
    intermediate_size: int = 9216            # the leading dense layers' MLP
    moe_intermediate_size: int = 1024
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256              # the router's outputs
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    #: ``(start, stop)`` of the routed experts this chip holds; None = all
    held_experts: Optional[Tuple[int, int]] = None
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0              # unused: no layer rotates
    #: positions between the snapshots a tail prefill leaves of the KDA
    #: layers' state (the module's docstring has why)
    snapshot_stride: int = 2048
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

    @property
    def kinds(self) -> Tuple[str, ...]:
        n = self.num_hidden_layers
        full = tuple(self.full_attn_layers) \
            if self.full_attn_layers is not None \
            else tuple(i for i in range(n) if i % 4 == 3 or i == n - 1)
        if any(not 0 <= i < n for i in full):
            raise ValueError(f"full_attn_layers {full} names a layer outside "
                             f"0..{n - 1}")
        return tuple(MLA if i in full else KDA for i in range(n))


def kimi_linear_tiny(**kw) -> KimiLinearConfig:
    """The CPU tests' preset: every mechanism, toy widths (a dense layer,
    then experts of which a quarter is held; latent attention at layer 3 of
    4; a snapshot every 16 positions)."""
    for k, v in dict(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, kda_num_heads=2,
            kda_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=4, held_experts=(0, 4),
            max_position_embeddings=256, snapshot_stride=16).items():
        kw.setdefault(k, v)
    return KimiLinearConfig(**kw)


def _l2norm(x):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


class KimiDeltaAttention(Layer):
    """The KDA operator.  ``conv [L, 3 H D]``: tap ``j`` multiplies column
    ``t - (L - 1) + j`` of ``[q~ | k~ | v~]``."""

    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.c = c
        h, H, D = c.hidden_size, c.kda_num_heads, c.kda_head_dim
        self.taps = c.short_conv_kernel_size
        self.q_proj, self.k_proj, self.v_proj = (
            _Linear(c, h, H * D) for _ in range(3))
        self.conv = self.create_parameter(
            [self.taps, 3 * H * D], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.f_a, self.f_b = _Linear(c, h, D), _Linear(c, D, H * D)
        self.g_a, self.g_b = _Linear(c, h, D), _Linear(c, D, H * D)
        self.b_proj = _Linear(c, h, H)
        self.A_log = self.create_parameter(
            [H], dtype=c.dtype, default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [H * D], dtype=c.dtype, default_initializer=I.Constant(0.0))
        self.o_norm = self.create_parameter(
            [D], dtype=c.dtype, default_initializer=I.Constant(1.0))
        self.o_proj = _Linear(c, H * D, h)

    def forward(self, x, cache_ctx=None):
        c = self.c
        B, S, _ = x.shape
        H, D = c.kda_num_heads, c.kda_head_dim
        z = jnp.concatenate([p(x) for p in (self.q_proj, self.k_proj,
                                            self.v_proj)], -1).astype(x.dtype)
        if cache_ctx is None:
            ext = jnp.pad(z, ((0, 0), (self.taps - 1, 0), (0, 0)))
            taps = [ext[:, j:j + S] for j in range(self.taps)]
        else:
            # every position's taps: the columns before the call's first
            # come from the group's shift side
            taps = cache_ctx.shift_state(z)
        fa = self.f_b(self.f_a(x).astype(x.dtype))
        ga = self.g_b(self.g_a(x).astype(x.dtype))
        with jax.named_scope(MIX_SCOPE):
            w = self.conv._value().astype(F32)
            qkv = jax.nn.silu(sum(w[j] * taps[j].astype(F32)
                                  for j in range(self.taps)))
            q, k, v = (qkv[..., i * H * D:(i + 1) * H * D].reshape(B, S, H, D)
                       for i in range(3))
            q, k = _l2norm(q) * D ** -0.5, _l2norm(k)
            g = -jnp.exp(self.A_log._value().astype(F32))[:, None] \
                * jax.nn.softplus(fa + self.dt_bias._value().astype(F32)
                                  ).reshape(B, S, H, D)
            beta = jax.nn.sigmoid(self.b_proj(x))              # [B, S, H]
        kw = dict(interpret=_interpret())
        if cache_ctx is None:
            zeros = jnp.zeros((H, D, D), F32)
            o = jnp.stack([kda_chunk_prefill(
                q[b], k[b], v[b], g[b], beta[b], zeros,
                jnp.zeros((1,), jnp.int32), S, **kw)[0] for b in range(B)])
        elif cache_ctx.mode == "prefill":
            s0, ends = cache_ctx.recurrent_start(S)
            n = jnp.sum(cache_ctx.live_tokens(S).astype(jnp.int32))
            o, last, kept = kda_chunk_prefill(
                q[0], k[0], v[0], g[0], beta[0], s0, ends, n, **kw)
            cache_ctx.recurrent_finish(S, last, kept)
            o = o[None]
        else:
            o = cache_ctx.recurrent_step(
                lambda state, active: kda_decode_step(
                    state, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    active, **kw))[:, None]
        with jax.named_scope(MIX_SCOPE):
            o = _rms(o, self.o_norm._value().astype(F32), c.rms_norm_eps) \
                * jax.nn.sigmoid(ga).reshape(B, S, H, D)
            o = o.reshape(B, S, H * D).astype(x.dtype)
        return self.o_proj(o)


class KimiLinearDecoderLayer(Layer):
    def __init__(self, c: KimiLinearConfig, index: int, kind: str):
        super().__init__()
        self.eps = c.rms_norm_eps

        def gain():
            return self.create_parameter([c.hidden_size], dtype=c.dtype,
                                         default_initializer=I.Constant(1.0))

        self.input_layernorm = gain()
        # the operator under its kind's name (a compiled program's op names
        # then say which kind a layer is)
        self.is_kda = kind == KDA
        if self.is_kda:
            self.kda = KimiDeltaAttention(c)
        else:
            self.self_attn = DeepseekV3Attention(c)
        self.post_attention_layernorm = gain()
        self.is_moe = index >= c.first_k_dense_replace
        self.mlp = DeepseekV3MoE(c) if self.is_moe \
            else DeepseekV3MLP(c, c.intermediate_size)

    def forward(self, x, cache_ctx=None):
        op = self.kda if self.is_kda else self.self_attn
        x = x + op(_rms(x, self.input_layernorm._value(), self.eps),
                   cache_ctx)
        m = _rms(x, self.post_attention_layernorm._value(), self.eps)
        return x + (self.mlp(m, cache_ctx) if self.is_moe else self.mlp(m))


class KimiLinearModel(Layer):
    def __init__(self, c: KimiLinearConfig):
        super().__init__()
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=_Normal(c.initializer_range))
        self.layers = LayerList([KimiLinearDecoderLayer(c, i, kind)
                                 for i, kind in enumerate(c.kinds)])
        self.norm = self.create_parameter(
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


class KimiLinearForCausalLM(Layer):
    """The decoder, the final norm and an untied head; logits float32."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = self.create_parameter(
            [config.hidden_size, config.vocab_size], dtype=config.dtype,
            default_initializer=_Normal(config.initializer_range))

    def cache_spec(self):
        """Stated by layer: the attention layers keep one latent vector
        ``[c_kv | k_r]`` a token, the KDA layers a state a slot of two sides
        — the last ``L - 1`` columns of ``[q~ | k~ | v~]`` (shifted by the
        pool) and the recurrence's ``S`` a head (float32; computed here,
        stored there) — with a snapshot every ``snapshot_stride`` positions.
        The latent group is named even where it has no layer: it counts the
        sequence's positions in blocks."""
        from ..serving.kv_cache import CacheGroup, CacheSpec

        c = self.config
        by_kind = {kind: tuple(i for i, k in enumerate(c.kinds) if k == kind)
                   for kind in (MLA, KDA)}
        groups = [CacheGroup(by_kind[MLA], ((1, c.latent_dim),))]
        if by_kind[KDA]:
            H, D = c.kda_num_heads, c.kda_head_dim
            groups.append(CacheGroup(
                by_kind[KDA],
                ((c.short_conv_kernel_size - 1, 3 * H * D), (H, D, D)),
                state=True, stride=c.snapshot_stride, chunk=CHUNK))
        return CacheSpec.by_layer(groups, kind="latent")

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

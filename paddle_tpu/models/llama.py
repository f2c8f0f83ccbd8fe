"""Llama decoder-only transformer family (RMSNorm + SwiGLU + rotary, GQA).

Reference parity: BASELINE.md configs #3 (Llama-2 7B, bf16 AMP-O2 + fused
flash-attn/rotary kernels) and #5 (Llama-2 70B auto-parallel).  The
reference snapshot predates Llama, so this is capability-matching against
the baseline configs, built from the same TP building blocks as GPT
(mp_layers.py) — not a translation of any reference file.

TPU-native design decisions (shared with gpt.py):
- Q and fused-KV projections are ColumnParallelLinear with head-major
  output layout: the sharded dim lands on the heads axis after reshape, so
  GSPMD keeps heads on the "model" axis through rotary + attention with
  zero resharding.  GQA: n_kv_heads may be < n_heads; both are sharded
  over the model axis (mp_degree must divide n_kv_heads).
- Rotary embedding through ops.pallas.rotary_embedding (rotate-half
  convention); cos/sin cached per (max_seq, head_dim, theta).
- Attention via ops.pallas.flash_attention (Pallas on TPU, XLA oracle
  elsewhere); GQA expands kv heads by repeat before the kernel — the
  repeat is free under jit on the sharded heads axis.
- SwiGLU MLP: gate/up fused in ONE ColumnParallelLinear of width 2*ffn
  (output laid out [2, ffn] so the split stays on the sharded axis),
  silu(gate) * up, then RowParallelLinear down.
- Sequence dim carries the "sep" axis (context parallelism, SURVEY §5.7).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.common import Dropout, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from ..ops.pallas import flash_attention as _flash_attention
from ..ops.pallas import rotary_embedding as _rotary_embedding
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
)
from ..distributed.fleet.utils.recompute import recompute
from ..distributed.sharding_spec import (
    BATCH_AXES, MODEL_AXIS, SEQ_AXIS, mark_sharding, set_param_spec,
)
from .gpt import GPTPretrainingCriterion


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None   # None → MHA
    intermediate_size: Optional[int] = None     # None → llama 8/3 rule
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    hidden_dropout_prob: float = 0.0
    tie_word_embeddings: bool = False
    recompute: bool = False
    # >1 enables chunked compute/collective overlap in every Megatron-TP
    # layer (distributed/fleet/meta_parallel/overlap.py); 1 = baseline
    tp_overlap_chunks: int = 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        # llama convention: 2/3 * 4h rounded up to a multiple of 256
        f = int(2 * 4 * self.hidden_size / 3)
        return 256 * ((f + 255) // 256)


def llama_tiny(**kw) -> LlamaConfig:
    kw.setdefault("vocab_size", 128)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_hidden_layers", 2)
    kw.setdefault("num_attention_heads", 4)
    kw.setdefault("num_key_value_heads", 2)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_position_embeddings", 64)
    return LlamaConfig(**kw)


def llama2_7b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("num_hidden_layers", 32)
    kw.setdefault("num_attention_heads", 32)
    kw.setdefault("intermediate_size", 11008)
    return LlamaConfig(**kw)


def llama2_13b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 5120)
    kw.setdefault("num_hidden_layers", 40)
    kw.setdefault("num_attention_heads", 40)
    kw.setdefault("intermediate_size", 13824)
    return LlamaConfig(**kw)


def llama2_70b(**kw) -> LlamaConfig:
    kw.setdefault("hidden_size", 8192)
    kw.setdefault("num_hidden_layers", 80)
    kw.setdefault("num_attention_heads", 64)
    kw.setdefault("num_key_value_heads", 8)
    kw.setdefault("intermediate_size", 28672)
    return LlamaConfig(**kw)


LLAMA_CONFIGS = {"tiny": llama_tiny, "llama2-7b": llama2_7b,
                 "llama2-13b": llama2_13b, "llama2-70b": llama2_70b}


def _act_spec(last=None):
    return P(BATCH_AXES, SEQ_AXIS, last)


def _rope_cache(seq_len: int, dim: int, theta: float):
    """cos/sin tables [S, D] for the rotate-half rotary convention.

    Pure numpy on purpose: the cache persists on the layer across traces,
    and a jnp value built inside a jit trace would be a leaked tracer."""
    inv_freq = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    t = np.arange(seq_len, dtype=np.float64)
    freqs = np.outer(t, inv_freq)                       # [S, D/2]
    emb = np.concatenate([freqs, freqs], axis=-1)       # [S, D]
    return (np.cos(emb).astype(np.float32), np.sin(emb).astype(np.float32))


class LlamaAttention(Layer):
    """Rotary causal self-attention with grouped-query KV."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.n_heads = config.num_attention_heads
        self.n_kv = config.n_kv_heads
        self.head_dim = config.head_dim
        h = config.hidden_size
        init = I.Normal(std=config.initializer_range)
        self.q_proj = ColumnParallelLinear(
            h, self.n_heads * self.head_dim, weight_attr=init,
            has_bias=False, gather_output=False,
            overlap_chunks=config.tp_overlap_chunks)
        # fused K+V, head-major [n_kv, 2*head_dim]
        self.kv_proj = ColumnParallelLinear(
            h, self.n_kv * 2 * self.head_dim, weight_attr=init,
            has_bias=False, gather_output=False,
            overlap_chunks=config.tp_overlap_chunks)
        self.o_proj = RowParallelLinear(
            h, h, weight_attr=init, has_bias=False, input_is_parallel=True,
            overlap_chunks=config.tp_overlap_chunks)
        self.rope_theta = config.rope_theta
        self.max_pos = config.max_position_embeddings
        self._rope = None  # built lazily at first forward

    def forward(self, x, cache_ctx=None):
        B, S, _ = x.shape
        q = self.q_proj(x).reshape([B, S, self.n_heads, self.head_dim])
        kv = self.kv_proj(x).reshape([B, S, self.n_kv, 2 * self.head_dim])
        q = mark_sharding(q, P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None))
        kv = mark_sharding(kv, P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None))
        k, v = kv.split(2, axis=-1)                     # [B,S,Hkv,D]

        if self._rope is None or self._rope[0].shape[0] < S:
            self._rope = _rope_cache(max(S, self.max_pos), self.head_dim,
                                     self.rope_theta)
        if cache_ctx is not None and cache_ctx.mode != "prefill":
            # position-offset rotary: gather the FULL tables at each
            # slot's current offset — decode's single query token (and
            # verify's k+1-token speculative window) is not at position 0
            cos = Tensor._wrap(jnp.asarray(self._rope[0]))
            sin = Tensor._wrap(jnp.asarray(self._rope[1]))
            q, k = _rotary_embedding(q, k, cos, sin,
                                     position_ids=cache_ctx.positions())
            # cache stores post-rotary K (and V) at kv-head granularity;
            # write + attend routed through the context (the paged cache
            # may run the Pallas flash-decoding kernel over its blocks;
            # verify mode routes to the W-token window attention)
            ctx = cache_ctx.decode_attention(q, k, v)
        else:
            pos = None if cache_ctx is None else \
                cache_ctx.prefill_positions(S)
            if pos is None:
                cos = Tensor._wrap(jnp.asarray(self._rope[0][:S]))
                sin = Tensor._wrap(jnp.asarray(self._rope[1][:S]))
                q, k = _rotary_embedding(q, k, cos, sin)
            else:
                # paged tail prefill: the bucket's tokens sit at absolute
                # offsets past the cached prefix — gather full tables
                cos = Tensor._wrap(jnp.asarray(self._rope[0]))
                sin = Tensor._wrap(jnp.asarray(self._rope[1]))
                q, k = _rotary_embedding(q, k, cos, sin, position_ids=pos)

            if cache_ctx is not None:                   # prefill
                # post-rotary K at kv-head granularity; attention routes
                # through the context (GQA expansion happens inside)
                cache_ctx.write_prefill(k, v)
                ctx = cache_ctx.prefill_attention(q, k, v)
            else:
                if self.n_kv != self.n_heads:
                    rep = self.n_heads // self.n_kv
                    k = k.unsqueeze(3) \
                         .expand([B, S, self.n_kv, rep, self.head_dim]) \
                         .reshape([B, S, self.n_heads, self.head_dim])
                    v = v.unsqueeze(3) \
                         .expand([B, S, self.n_kv, rep, self.head_dim]) \
                         .reshape([B, S, self.n_heads, self.head_dim])

                ctx = _flash_attention(q, k, v, is_causal=True,
                                       training=self.training)
        ctx = mark_sharding(ctx, P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None))
        ctx = ctx.reshape([B, S, self.n_heads * self.head_dim])
        return self.o_proj(ctx)


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)); gate/up fused column-parallel."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.ffn = config.ffn_size
        self.gate_up_proj = ColumnParallelLinear(
            config.hidden_size, 2 * config.ffn_size, weight_attr=init,
            has_bias=False, gather_output=False,
            overlap_chunks=config.tp_overlap_chunks)
        self.down_proj = RowParallelLinear(
            config.ffn_size, config.hidden_size, weight_attr=init,
            has_bias=False, input_is_parallel=True,
            overlap_chunks=config.tp_overlap_chunks)

    def forward(self, x):
        gu = self.gate_up_proj(x)
        gate, up = gu.split(2, axis=-1)
        return self.down_proj(F.silu(gate) * up)


class LlamaDecoderLayer(Layer):
    """Pre-RMSNorm block: x + attn(norm(x)); x + mlp(norm(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, cache_ctx=None):
        x = x + self.dropout(
            self.self_attn(self.input_layernorm(x), cache_ctx))
        x = x + self.dropout(self.mlp(self.post_attention_layernorm(x)))
        return mark_sharding(x, _act_spec())


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        init = I.Normal(std=config.initializer_range)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=init,
            overlap_chunks=config.tp_overlap_chunks)
        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, cache_ctx=None):
        h = mark_sharding(self.embed_tokens(input_ids), _act_spec())
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
                h = layer(h, cache_ctx)
            elif self.config.recompute and self.training:
                h = recompute(layer, h)
            else:
                h = layer(h)
        return self.norm(h)


class LlamaForCausalLM(Layer):
    """LlamaModel + LM head (untied by default, per llama convention)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            set_param_spec(self.lm_head.weight, P(None, MODEL_AXIS))

    def cache_spec(self):
        """What serving caches a token a layer: K and V per KV head."""
        from ..serving.kv_cache import CacheSpec

        c = self.config
        return CacheSpec.kv(c.num_hidden_layers, c.n_kv_heads, c.head_dim)

    def forward(self, input_ids, cache_ctx=None):
        h = self.llama(input_ids, cache_ctx=cache_ctx)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = h.matmul(self.llama.embed_tokens.weight.t())
        return mark_sharding(logits, _act_spec(last=MODEL_AXIS))


class _LlamaHeadPipe(Layer):
    """Final RMSNorm + LM head for the pipelined model."""

    def __init__(self, config: LlamaConfig, embed_tokens=None):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        if embed_tokens is None:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            set_param_spec(self.lm_head.weight, P(None, MODEL_AXIS))
        else:
            self.lm_head = None
            object.__setattr__(self, "_tied_embeddings", embed_tokens)

    def forward(self, x):
        h = self.norm(x)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = h.matmul(self._tied_embeddings.weight.t())
        return mark_sharding(logits, _act_spec(last=MODEL_AXIS))


class _LlamaEmbPipe(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=init)

    def forward(self, input_ids):
        return mark_sharding(self.embed_tokens(input_ids), _act_spec())


def LlamaForCausalLMPipe(config: LlamaConfig, topology=None,
                         num_stages: Optional[int] = None,
                         recompute_interval: int = 0):
    """Pipeline-parallel Llama (same PipelineLayer machinery as GPT)."""
    from ..distributed.fleet.meta_parallel.parallel_layers.pp_layers import (
        PipelineLayer,
    )
    emb = _LlamaEmbPipe(config)
    layers = [emb]
    layers += [LlamaDecoderLayer(config)
               for _ in range(config.num_hidden_layers)]
    tied = emb.embed_tokens if config.tie_word_embeddings else None
    layers.append(_LlamaHeadPipe(config, tied))
    crit = GPTPretrainingCriterion()
    return PipelineLayer(
        layers, num_stages=num_stages, topology=topology,
        loss_fn=lambda logits, labels: crit(logits, labels),
        recompute_interval=recompute_interval)


LlamaPretrainingCriterion = GPTPretrainingCriterion

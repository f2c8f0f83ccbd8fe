"""GPT decoder-only transformer — the flagship model family.

Reference parity: the GPT used across the reference's hybrid-parallel and
auto-parallel tests (unittests/auto_parallel_gpt_model.py; fused kernels
operators/fused/fused_attention_op.cu, fused_feedforward_op) and the
Megatron construction of mp_layers.py.

TPU-native design decisions:
- Q/K/V is ONE ColumnParallelLinear of width 3*hidden whose output dim is
  laid out head-major [n_heads, 3*head_dim]: after reshape the sharded dim
  lands on n_heads, so GSPMD keeps heads on the "model" axis through the
  whole attention block with zero resharding (a fused-qkv layout the
  reference implements inside fused_attention with per-rank slicing).
- Attention runs through ops.pallas.flash_attention (Pallas kernel on TPU,
  XLA oracle elsewhere); is_causal=True, no materialized [S,S] mask.
- Sequence dim carries the "sep" axis (context parallelism — capability
  beyond the reference, SURVEY.md §5.7).
- Activation recompute per decoder layer via fleet recompute
  (jax.checkpoint) when config.recompute is on.
- LM head ties the vocab-parallel embedding weight (SharedLayerDesc
  semantics without the grad-sync machinery: one parameter object).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import LayerNorm
from ..ops.pallas import flash_attention_qkv as _flash_attention_qkv
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy,
)
from ..distributed.fleet.utils.recompute import recompute
from ..distributed.sharding_spec import (
    BATCH_AXES, MODEL_AXIS, SEQ_AXIS, mark_sharding, set_param_spec,
)
from .held_experts import EMBED_SCOPE, HEAD_SCOPE


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: Optional[int] = None  # default 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    recompute: bool = False
    # >1 enables chunked compute/collective overlap in every Megatron-TP
    # layer (distributed/fleet/meta_parallel/overlap.py); 1 = baseline
    tp_overlap_chunks: int = 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size


def gpt_tiny(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0, **kw)


def gpt2_345m(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=1024,
                     num_hidden_layers=24, num_attention_heads=16,
                     max_position_embeddings=1024, **kw)


def gpt3_13b(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=5120,
                     num_hidden_layers=40, num_attention_heads=40,
                     max_position_embeddings=2048, **kw)


GPT_CONFIGS = {"tiny": gpt_tiny, "gpt2-345m": gpt2_345m, "gpt3-13b": gpt3_13b}


def _act_spec(last=None):
    return P(BATCH_AXES, SEQ_AXIS, last)


class GPTAttention(Layer):
    """Causal self-attention, heads sharded over the model axis."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.n_heads = config.num_attention_heads
        self.head_dim = config.head_dim
        h = config.hidden_size
        init = I.Normal(std=config.initializer_range)
        # fused qkv, head-major output layout [n_heads, 3*head_dim]
        self.qkv_proj = ColumnParallelLinear(
            h, 3 * h, weight_attr=init, gather_output=False,
            overlap_chunks=config.tp_overlap_chunks)
        self.out_proj = RowParallelLinear(
            h, h, weight_attr=init, input_is_parallel=True,
            overlap_chunks=config.tp_overlap_chunks)
        self.dropout_p = config.attention_probs_dropout_prob

    def forward(self, x, cache_ctx=None):
        B, S, _ = x.shape
        qkv = self.qkv_proj(x)                                  # [B,S,3h]/mp
        qkv = qkv.reshape([B, S, self.n_heads, 3 * self.head_dim])
        qkv = mark_sharding(qkv, P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None))
        if cache_ctx is None:
            # nothing to cache: the kernels read q, k and v out of the
            # projection as it is, no split stands between them
            ctx = _flash_attention_qkv(
                qkv, dropout_p=self.dropout_p, is_causal=True,
                training=self.training)
        else:
            q, k, v = qkv.split(3, axis=-1)                     # [B,S,H,D]
            if cache_ctx.mode == "prefill":
                # prompt forward writes K/V into the cache; attention
                # routes through the context — gather-by-block-table with a
                # cached-prefix mask over the engine's pool (the tail bucket
                # attends onto shared blocks), ordinary causal for the
                # speculative draft's dense cache
                cache_ctx.write_prefill(k, v)
                ctx = cache_ctx.prefill_attention(q, k, v)
            else:           # decode (S == 1) or verify (S == k+1) window
                # write + attend routed through the context: the pool
                # streams blocks through the Pallas flash-decoding kernel
                # instead of gathering a copy of each slot's sequence;
                # verify mode routes the same call to the cache's W-token
                # speculative window attention — models stay single-path
                ctx = cache_ctx.decode_attention(q, k, v)
        ctx = mark_sharding(ctx, P(BATCH_AXES, SEQ_AXIS, MODEL_AXIS, None))
        ctx = ctx.reshape([B, S, self.n_heads * self.head_dim])
        return self.out_proj(ctx)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.fc1 = ColumnParallelLinear(
            config.hidden_size, config.ffn_size, weight_attr=init,
            gather_output=False,
            overlap_chunks=config.tp_overlap_chunks)
        self.fc2 = RowParallelLinear(
            config.ffn_size, config.hidden_size, weight_attr=init,
            input_is_parallel=True,
            overlap_chunks=config.tp_overlap_chunks)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(Layer):
    """Pre-LN block (reference: fused_attention + fused_feedforward
    semantics: LN → attn → dropout → residual; LN → mlp → dropout →
    residual)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        eps = config.layer_norm_epsilon
        self.ln1 = LayerNorm(config.hidden_size, epsilon=eps)
        self.attn = GPTAttention(config)
        self.ln2 = LayerNorm(config.hidden_size, epsilon=eps)
        self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, cache_ctx=None):
        x = x + self.dropout(self.attn(self.ln1(x), cache_ctx))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return mark_sharding(x, _act_spec())


class GPTEmbeddings(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = I.Normal(std=config.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size, weight_attr=init,
            overlap_chunks=config.tp_overlap_chunks)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=init)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            S = input_ids.shape[-1]
            max_pos = self.position_embeddings._num_embeddings
            if S > max_pos:
                raise ValueError(
                    f"sequence length {S} exceeds max_position_embeddings "
                    f"{max_pos}")
            position_ids = Tensor._wrap(jnp.arange(S)[None, :])
        h = self.word_embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        return self.dropout(mark_sharding(h, _act_spec()))


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_hidden_layers)])
        self.final_ln = LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, cache_ctx=None):
        if cache_ctx is not None and position_ids is None:
            if cache_ctx.mode != "prefill":
                # decode: each slot's single token sits at that slot's
                # own offset ([slots, 1]); verify: the speculative
                # window's k+1 tokens likewise ([slots, k+1])
                position_ids = cache_ctx.positions()
            else:
                # tail prefill: tokens sit past the cached prefix
                # (None from the draft's dense cache — default 0..S-1)
                position_ids = cache_ctx.prefill_positions(
                    input_ids.shape[-1])
        with jax.named_scope(EMBED_SCOPE):
            h = self.embeddings(input_ids, position_ids)
        for i, layer in enumerate(self.layers):
            if cache_ctx is not None:
                cache_ctx.layer_idx = i
                h = layer(h, cache_ctx)
            elif self.config.recompute and self.training:
                h = recompute(layer, h)
            else:
                h = layer(h)
        with jax.named_scope(HEAD_SCOPE):
            return self.final_ln(h)


class GPTForCausalLM(Layer):
    """GPTModel + LM head (tied to the vocab-parallel embedding by
    default)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            set_param_spec(self.lm_head.weight, P(None, MODEL_AXIS))
        else:
            self.lm_head = None

    def cache_spec(self):
        """What serving caches a token a layer: K and V of every head."""
        from ..serving.kv_cache import CacheSpec

        c = self.config
        return CacheSpec.kv(c.num_hidden_layers, c.num_attention_heads,
                            c.head_dim)

    def forward(self, input_ids, position_ids=None, cache_ctx=None):
        h = self.gpt(input_ids, position_ids, cache_ctx=cache_ctx)
        with jax.named_scope(HEAD_SCOPE):
            if self.lm_head is not None:
                logits = self.lm_head(h)
            else:
                w = self.gpt.embeddings.word_embeddings.weight
                logits = h.matmul(w.t())
            return mark_sharding(logits, _act_spec(last=MODEL_AXIS))

    def compute_loss(self, input_ids, labels, loss_mask=None,
                     position_ids=None, ignore_index: int = -100):
        """Forward + causal-LM loss without materializing [B,S,V] logits.

        Uses ops.fused.fused_linear_cross_entropy (vocab-blockwise streamed
        CE — the memory fusion behind the reference's
        c_softmax_with_cross_entropy path) whenever the head weight is not
        vocab-sharded; under tensor parallelism it falls back to the
        vocab-parallel logits + ParallelCrossEntropy path.
        """
        from ..distributed import mesh as _mesh_mod
        from ..distributed.fleet.meta_parallel.tensor_parallel import (
            shard_batch,
        )
        from ..ops.fused import fused_linear_cross_entropy

        m = _mesh_mod.get_global_mesh()
        # same input placement the DataParallel wrapper's forward applies
        # (callers reach this method through the wrapper's __getattr__)
        input_ids = shard_batch(input_ids, m)
        labels = shard_batch(labels, m)
        if loss_mask is not None:
            loss_mask = shard_batch(loss_mask, m)
        mp = m.shape.get(MODEL_AXIS, 1) if m is not None else 1
        if mp > 1:
            # the criterion is built lazily, after apply_tp_overlap has
            # already stamped the model — read the root's stamp (or the
            # config) so the CE rides the chunked schedule too
            chunks = getattr(self, "_tp_overlap_chunks", 0) \
                or self.config.tp_overlap_chunks
            crit = GPTPretrainingCriterion(ignore_index=ignore_index,
                                           overlap_chunks=chunks)
            return crit(self.forward(input_ids, position_ids), labels,
                        loss_mask)
        h = self.gpt(input_ids, position_ids)
        if self.lm_head is not None:
            return fused_linear_cross_entropy(
                h, self.lm_head.weight, labels, loss_mask=loss_mask,
                ignore_index=ignore_index, transpose_weight=True)
        w = self.gpt.embeddings.word_embeddings.weight
        return fused_linear_cross_entropy(
            h, w, labels, loss_mask=loss_mask, ignore_index=ignore_index)


class _GPTHeadPipe(Layer):
    """Final LN + LM head for the pipelined model.  The tied embedding
    weight is referenced without sublayer registration (single-controller
    sharing — SharedLayerDesc semantics, pp_layers.py:77)."""

    def __init__(self, config: GPTConfig, word_embeddings=None):
        super().__init__()
        self.final_ln = LayerNorm(config.hidden_size,
                                  epsilon=config.layer_norm_epsilon)
        if word_embeddings is None:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)
            set_param_spec(self.lm_head.weight, P(None, MODEL_AXIS))
        else:
            self.lm_head = None
            object.__setattr__(self, "_tied_embeddings", word_embeddings)

    def forward(self, x):
        h = self.final_ln(x)
        if self.lm_head is not None:
            logits = self.lm_head(h)
        else:
            logits = h.matmul(self._tied_embeddings.weight.t())
        return mark_sharding(logits, _act_spec(last=MODEL_AXIS))


def GPTForCausalLMPipe(config: GPTConfig, topology=None,
                       num_stages: Optional[int] = None,
                       recompute_interval: int = 0,
                       num_virtual_pipeline_stages: Optional[int] = None):
    """Pipeline-parallel GPT (reference: the GPTForCausalLMPipe pattern of
    hybrid_parallel_pp_transformer.py) — a PipelineLayer whose uniform
    decoder stack compiles onto the "pipe" mesh axis.
    num_virtual_pipeline_stages > 1 selects the interleaved 1F1B schedule
    (reference pp_layers.py:162 interleaved segmentation)."""
    from ..distributed.fleet.meta_parallel.parallel_layers.pp_layers import (
        PipelineLayer,
    )
    emb = GPTEmbeddings(config)
    layers = [emb]
    layers += [GPTDecoderLayer(config)
               for _ in range(config.num_hidden_layers)]
    tied = emb.word_embeddings if config.tie_word_embeddings else None
    layers.append(_GPTHeadPipe(config, tied))
    crit = GPTPretrainingCriterion()
    return PipelineLayer(
        layers, num_stages=num_stages, topology=topology,
        loss_fn=lambda logits, labels: crit(logits, labels),
        recompute_interval=recompute_interval,
        num_virtual_pipeline_stages=num_virtual_pipeline_stages)


class GPTPretrainingCriterion(Layer):
    """Vocab-parallel causal-LM loss (reference:
    auto_parallel_gpt_model.py GPTPretrainingCriterion)."""

    def __init__(self, ignore_index: int = -100, overlap_chunks: int = 1):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=ignore_index,
                                       overlap_chunks=overlap_chunks)
        self.ignore_index = ignore_index

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)          # [B, S, 1]
        loss = loss.squeeze(-1)
        if loss_mask is not None:
            m = loss_mask.astype("float32")
            return (loss * m).sum() / m.sum().clip(min=1.0)
        denom = (labels != self.ignore_index).astype("float32").sum()
        return loss.sum() / denom.clip(min=1.0)

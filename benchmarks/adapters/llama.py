"""Where the benchmark meets the program for the ``llama`` family (the code
that runs Mistral-7B-v0.3): builds the program's model and lays the seeded
weight tree of ``references/llama.py`` out under the program's ``state_dict``
keys.  The program fuses K and V head-major ``[kv_heads, 2 * head_dim]`` and
gate and up side by side ``[2, ffn]``."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        intermediate_size=int(config["intermediate_size"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        initializer_range=float(config["initializer_range"]),
        tie_word_embeddings=bool(config["tie_word_embeddings"]))


def build_model(config: dict):
    from paddle_tpu.models import LlamaForCausalLM

    return LlamaForCausalLM(program_config(config))


def _layer(lw: dict, kv_heads: int) -> dict:
    h = lw["attn.wk"].shape[0]
    kv = jnp.stack([lw["attn.wk"].reshape(h, kv_heads, -1),
                    lw["attn.wv"].reshape(h, kv_heads, -1)], axis=-2)
    return {
        "input_layernorm.weight": lw["input_norm.g"],
        "self_attn.q_proj.weight": lw["attn.wq"],
        "self_attn.kv_proj.weight": kv.reshape(h, -1),
        "self_attn.o_proj.weight": lw["attn.wo"],
        "post_attention_layernorm.weight": lw["post_norm.g"],
        "mlp.gate_up_proj.weight": jnp.concatenate(
            [lw["mlp.w_gate"], lw["mlp.w_up"]], axis=1),
        "mlp.down_proj.weight": lw["mlp.w_down"],
    }


_layer_jit = jax.jit(_layer, static_argnames=("kv_heads",))


def program_leaves(tree: dict, d: dict):
    from benchmarks.references.llama import layer_weights

    yield "llama.embed_tokens.weight", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i),
                               kv_heads=d["kv_heads"]).items():
            yield f"llama.layers.{i}.{k}", v
    yield "llama.norm.weight", tree["norm.g"]
    yield "lm_head.weight", tree["lm_head"]

"""Where the benchmark meets the program for the ``keye_vl2`` family (the code
that runs Keye-VL-2.0-30B-A3B's language model): builds the program's model,
in the dtype it is served in, and lays the seeded weight tree of
``references/keye_vl2.py`` out under the program's ``state_dict`` keys.  The
program keeps an expert's gate and up side by side and the held experts
stacked; every other leaf is the reference's as it is."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.keye_vl2 import KeyeVL2Config

    sa = config["sa_config"]
    return KeyeVL2Config(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        num_experts=int(config["router_experts"]),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        held_experts=tuple(int(x) for x in config["held_experts"]),
        indexer_num_heads=int(sa["indexer_num_heads"]),
        indexer_head_dim=int(sa["indexer_head_dim"]),
        indexer_topk=int(sa["topk"]),
        mrope_section=tuple(int(x) for x in
                            config["rope_scaling"]["mrope_section"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.keye_vl2 import KeyeVL2ForCausalLM

    return KeyeVL2ForCausalLM(program_config(config))


_PLAIN = {
    "input_norm.g": "input_layernorm",
    "attn.wq": "self_attn.q_proj", "attn.wk": "self_attn.k_proj",
    "attn.wv": "self_attn.v_proj", "attn.wo": "self_attn.o_proj",
    "attn.q_norm.g": "self_attn.q_norm", "attn.k_norm.g": "self_attn.k_norm",
    "idx.wq": "self_attn.indexer_q_proj",
    "idx.wk": "self_attn.indexer_k_proj",
    "idx.k_norm.g": "self_attn.indexer_k_norm",
    "idx.k_norm.b": "self_attn.indexer_k_norm_bias",
    "idx.ww": "self_attn.indexer_weights_proj",
    "post_norm.g": "post_attention_layernorm",
    "moe.router": "mlp.gate", "moe.w_down": "mlp.experts_down",
}


def _layer(lw: dict) -> dict:
    out = {theirs: lw[ours] for ours, theirs in _PLAIN.items()}
    out["mlp.experts_gate_up"] = jnp.concatenate(
        [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)
    return out


_layer_jit = jax.jit(_layer)


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.keye_vl2 import layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d)).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.norm", tree["norm.g"]
    yield "lm_head", tree["lm_head"]

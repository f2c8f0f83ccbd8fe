"""Where the benchmark meets the program for the ``mellum`` family (the code
that runs Mellum2-12B-A2.5B): builds the program's model, in the dtype it is
served in, and lays the seeded weight tree of ``references/mellum.py`` out
under the program's ``state_dict`` keys.  The program keeps q, k and v
output-major, an expert's gate and up side by side and the held experts
stacked; every other leaf is the reference's as it is."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.mellum import MellumConfig

    rp = config["rope_parameters"]
    yarn = rp["full_attention"]
    return MellumConfig(
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
        layer_types=tuple(config["layer_types"]),
        sliding_window=int(config["sliding_window"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(rp["sliding_attention"]["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original_max_position_embeddings=int(
            yarn["original_max_position_embeddings"]),
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=float(yarn["attention_factor"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.mellum import MellumForCausalLM

    return MellumForCausalLM(program_config(config))


_PLAIN = {
    "input_norm.g": "input_layernorm",
    "attn.wo": "self_attn.o_proj",
    "attn.q_norm.g": "self_attn.q_norm", "attn.k_norm.g": "self_attn.k_norm",
    "post_norm.g": "post_attention_layernorm",
    "moe.router": "mlp.gate", "moe.w_down": "mlp.experts_down",
}
_TRANSPOSED = {"attn.wq": "self_attn.q_proj", "attn.wk": "self_attn.k_proj",
               "attn.wv": "self_attn.v_proj"}


def _layer(lw: dict) -> dict:
    out = {theirs: lw[ours] for ours, theirs in _PLAIN.items()}
    out.update({theirs: lw[ours].T for ours, theirs in _TRANSPOSED.items()})
    out["mlp.experts_gate_up"] = jnp.concatenate(
        [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)
    return out


_layer_jit = jax.jit(_layer)


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.mellum import layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d)).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.norm", tree["norm.g"]
    yield "lm_head", tree["lm_head"]

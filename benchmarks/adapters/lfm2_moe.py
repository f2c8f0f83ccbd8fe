"""Where the benchmark meets the program for the ``lfm2_moe`` family (the code
that runs LFM2-24B-A2B): builds the program's model, in the dtype it is served
in, and lays the seeded weight tree of ``references/lfm2_moe.py`` out under
the program's ``state_dict`` keys, a layer at a time.  The program keeps q, k
and v output-major, a feed-forward's gate and up side by side and the held
experts stacked; every other leaf is the reference's as it is (the head is the
embedding in both)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.lfm2 import Lfm2Config

    return Lfm2Config(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        intermediate_size=int(config["intermediate_size"]),
        num_dense_layers=int(config["num_dense_layers"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        num_experts=int(config["router_experts"]),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        held_experts=tuple(int(x) for x in config["held_experts"]),
        layer_types=tuple(config["layer_types"]),
        conv_L_cache=int(config["conv_L_cache"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        norm_eps=float(config["norm_eps"]),
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.lfm2 import Lfm2ForCausalLM

    return Lfm2ForCausalLM(program_config(config))


_PLAIN = {
    "operator_norm.g": "operator_norm", "ffn_norm.g": "ffn_norm",
    "conv.w_in": "conv.in_proj.weight", "conv.filter": "conv.filter",
    "conv.w_out": "conv.out_proj.weight",
    "attn.wo": "self_attn.out_proj",
    "attn.q_norm.g": "self_attn.q_layernorm",
    "attn.k_norm.g": "self_attn.k_layernorm",
    "mlp.w_down": "feed_forward.down_proj",
    "moe.router": "feed_forward.gate", "moe.bias": "feed_forward.expert_bias",
    "moe.w_down": "feed_forward.experts_down",
}
_TRANSPOSED = {"attn.wq": "self_attn.q_proj", "attn.wk": "self_attn.k_proj",
               "attn.wv": "self_attn.v_proj"}


def _layer(lw: dict) -> dict:
    out = {theirs: lw[ours] for ours, theirs in _PLAIN.items() if ours in lw}
    out.update({theirs: lw[ours].T for ours, theirs in _TRANSPOSED.items()
                if ours in lw})
    if "mlp.w_gate" in lw:
        out["feed_forward.gate_up_proj"] = jnp.concatenate(
            [lw["mlp.w_gate"], lw["mlp.w_up"]], axis=1)
    else:
        out["feed_forward.experts_gate_up"] = jnp.concatenate(
            [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)
    return out


_layer_jit = jax.jit(_layer)


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.lfm2_moe import layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d)).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.embedding_norm", tree["norm.g"]

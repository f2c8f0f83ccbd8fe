"""Where the benchmark meets the program for the ``deepseek_v3`` family (the
code that runs JoyAI-LLM-Flash): builds the program's model, in the dtype it
is served in, and lays the seeded weight tree of ``references/deepseek_v3.py``
out under the program's ``state_dict`` keys.  The program keeps the published
``kv_b_proj`` per head in the two halves its absorbed attention multiplies by
(``w_uk [H, nope, rank]``, ``w_uv [H, rank, v]``), gate and up side by side,
and the held experts stacked."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config

    return DeepseekV3Config(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        q_lora_rank=int(config["q_lora_rank"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        n_routed_experts=int(config["router_experts"]),
        num_experts_per_tok=int(config["num_experts_per_tok"]),
        n_shared_experts=int(config["n_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        held_experts=tuple(int(x) for x in config["held_experts"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM

    return DeepseekV3ForCausalLM(program_config(config))


def _layer(lw: dict, heads: int, nope: int) -> dict:
    rank = lw["attn.wkv_b"].shape[0]
    kvb = lw["attn.wkv_b"].reshape(rank, heads, -1)
    out = {
        "input_layernorm": lw["input_norm.g"],
        "self_attn.q_a_proj": lw["attn.wq_a"],
        "self_attn.q_a_layernorm": lw["attn.q_norm.g"],
        "self_attn.q_b_proj": lw["attn.wq_b"],
        "self_attn.kv_a_proj_with_mqa": lw["attn.wkv_a"],
        "self_attn.kv_a_layernorm": lw["attn.kv_norm.g"],
        "self_attn.w_uk": kvb[:, :, :nope].transpose(1, 2, 0),
        "self_attn.w_uv": kvb[:, :, nope:].transpose(1, 0, 2),
        "self_attn.o_proj": lw["attn.wo"],
        "post_attention_layernorm": lw["post_norm.g"],
    }
    if "mlp.w_gate" in lw:
        out["mlp.gate_up_proj"] = jnp.concatenate(
            [lw["mlp.w_gate"], lw["mlp.w_up"]], axis=1)
        out["mlp.down_proj"] = lw["mlp.w_down"]
    else:
        out["mlp.gate"] = lw["moe.router"]
        out["mlp.e_score_correction_bias"] = lw["moe.bias"]
        out["mlp.experts_gate_up"] = jnp.concatenate(
            [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)
        out["mlp.experts_down"] = lw["moe.w_down"]
        out["mlp.shared_experts.gate_up_proj"] = jnp.concatenate(
            [lw["moe.shared.w_gate"], lw["moe.shared.w_up"]], axis=1)
        out["mlp.shared_experts.down_proj"] = lw["moe.shared.w_down"]
    return out


_layer_jit = jax.jit(_layer, static_argnames=("heads", "nope"))


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.deepseek_v3 import layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d), heads=d["heads"],
                               nope=d["nope"]).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.norm", tree["norm.g"]
    yield "lm_head", tree["lm_head"]

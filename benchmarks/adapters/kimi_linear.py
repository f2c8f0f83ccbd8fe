"""Where the benchmark meets the program for the ``kimi_linear`` family (the
code that runs Kimi-Linear-48B-A3B): builds the program's model, in the dtype
it is served in, and lays the seeded weight tree of
``references/kimi_linear.py`` out under the program's ``state_dict`` keys, a
layer at a time.  The program keeps the published ``kv_b_proj`` per head in
its two halves, a feed-forward's gate and up side by side, the held experts
stacked, and ``A_log`` / ``dt_bias`` as the reference derives them from their
seeded leaves (``gate_parameters``); every other leaf is the reference's as
it is."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models.kimi_linear import KimiLinearConfig

    la = config["linear_attn_config"]
    return KimiLinearConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_nope_head_dim=int(config["qk_nope_head_dim"]),
        qk_rope_head_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        mla_use_nope=bool(config["mla_use_nope"]),
        kda_num_heads=int(la["num_heads"]),
        kda_head_dim=int(la["head_dim"]),
        short_conv_kernel_size=int(la["short_conv_kernel_size"]),
        full_attn_layers=tuple(int(i) - 1 for i in la["full_attn_layers"]),
        intermediate_size=int(config["intermediate_size"]),
        moe_intermediate_size=int(config["moe_intermediate_size"]),
        first_k_dense_replace=int(config["first_k_dense_replace"]),
        n_routed_experts=int(config["router_experts"]),
        num_experts_per_tok=int(config["num_experts_per_token"]),
        n_shared_experts=int(config["num_shared_experts"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        held_experts=tuple(int(x) for x in config["held_experts"]),
        max_position_embeddings=int(config["model_max_length"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        snapshot_stride=int(config["snapshot_stride"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.kimi_linear import KimiLinearForCausalLM

    return KimiLinearForCausalLM(program_config(config))


_KDA = {"kda.wq": "q_proj.weight", "kda.wk": "k_proj.weight",
        "kda.wv": "v_proj.weight", "kda.conv": "conv",
        "kda.w_fa": "f_a.weight", "kda.w_fb": "f_b.weight",
        "kda.w_beta": "b_proj.weight", "kda.w_ga": "g_a.weight",
        "kda.w_gb": "g_b.weight", "kda.o_norm.g": "o_norm",
        "kda.wo": "o_proj.weight"}
_MLA = {"attn.wq": "q_proj", "attn.wkv_a": "kv_a_proj_with_mqa",
        "attn.kv_norm.g": "kv_a_layernorm", "attn.wo": "o_proj"}


def _layer(lw: dict, heads: int, nope: int) -> dict:
    from benchmarks.references.kimi_linear import gate_parameters

    out = {"input_layernorm": lw["input_norm.g"],
           "post_attention_layernorm": lw["post_norm.g"]}
    if "kda.wq" in lw:
        out.update({f"kda.{theirs}": lw[ours]
                    for ours, theirs in _KDA.items()})
        out["kda.A_log"], out["kda.dt_bias"] = gate_parameters(
            lw["kda.a"], lw["kda.dt"])
    else:
        out.update({f"self_attn.{theirs}": lw[ours]
                    for ours, theirs in _MLA.items()})
        rank = lw["attn.wkv_b"].shape[0]
        kvb = lw["attn.wkv_b"].reshape(rank, heads, -1)
        out["self_attn.w_uk"] = kvb[:, :, :nope].transpose(1, 2, 0)
        out["self_attn.w_uv"] = kvb[:, :, nope:].transpose(1, 0, 2)
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
    from benchmarks.references.kimi_linear import layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d), heads=d["heads"],
                               nope=d["nope"]).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.norm", tree["norm.g"]
    yield "lm_head", tree["lm_head"]

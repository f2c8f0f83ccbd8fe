"""Where the benchmark meets the program for the ``evabyte`` family (the code
that runs EvaByte): builds the program's model, in the dtype it is served in,
and lays the seeded weight tree of ``references/evabyte.py`` out under the
program's ``state_dict`` keys.  The program keeps the q, k and v projections
output-major (the reference's matrices transposed), its norms' ``g`` and its
pooling vectors as the reference derives them from their seeded leaves
(``gain``, ``pooling_vector``: the same numbers on both sides); every other
leaf is the reference's as it is."""
from __future__ import annotations

import jax


def program_config(config: dict):
    from paddle_tpu.models.evabyte import EvaByteConfig

    return EvaByteConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        num_hidden_layers=int(config["num_hidden_layers"]),
        num_attention_heads=int(config["num_attention_heads"]),
        num_key_value_heads=int(config["num_key_value_heads"]),
        intermediate_size=int(config["intermediate_size"]),
        window_size=int(config["window_size"]),
        chunk_size=int(config["chunk_size"]),
        num_pred_heads=int(config["num_pred_heads"]),
        max_position_embeddings=int(config["max_position_embeddings"]),
        rms_norm_eps=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        dtype=str(config.get("torch_dtype", "bfloat16")))


def build_model(config: dict):
    from paddle_tpu.models.evabyte import EvaByteForCausalLM

    return EvaByteForCausalLM(program_config(config))


_PLAIN = {"attn.wo": "self_attn.o_proj", "mlp.w_gate": "mlp.gate_proj",
          "mlp.w_up": "mlp.up_proj", "mlp.w_down": "mlp.down_proj"}
_TRANSPOSED = {"attn.wq": "self_attn.q_proj", "attn.wk": "self_attn.k_proj",
               "attn.wv": "self_attn.v_proj"}
_GAINS = {"input_norm.g": "input_layernorm",
          "post_norm.g": "post_attention_layernorm"}
_POOLING = {"attn.phi": "self_attn.summary_phi",
            "attn.mu": "self_attn.summary_mu"}


def _layer(lw: dict) -> dict:
    from benchmarks.references.evabyte import gain, pooling_vector

    out = {theirs: lw[ours] for ours, theirs in _PLAIN.items()}
    out.update({theirs: lw[ours].T for ours, theirs in _TRANSPOSED.items()})
    out.update({theirs: gain(lw[ours]).astype(lw[ours].dtype)
                for ours, theirs in _GAINS.items()})
    out.update({theirs: pooling_vector(lw[ours]).astype(lw[ours].dtype)
                for ours, theirs in _POOLING.items()})
    return out


_layer_jit = jax.jit(_layer)


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.evabyte import gain, layer_weights

    yield "model.embed_tokens", tree["embed"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i, d)).items():
            yield f"model.layers.{i}.{k}", v
    yield "model.norm", gain(tree["norm.g"]).astype(tree["norm.g"].dtype)
    yield "lm_head", tree["lm_head"]

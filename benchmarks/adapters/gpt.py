"""Where the benchmark meets the program for the ``gpt`` family: builds the
program's model from a configuration file and lays the benchmark's seeded
weight tree (named as ``references/gpt.py`` names it) out under the program's
``state_dict`` keys — a checkpoint loader, nothing more.  The program fuses
Q, K and V into one projection whose output is head-major
``[heads, 3 * head_dim]``."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program_config(config: dict):
    from paddle_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=int(config["vocab_size"]), hidden_size=int(config["n_embd"]),
        num_hidden_layers=int(config["n_layer"]),
        num_attention_heads=int(config["n_head"]),
        max_position_embeddings=int(config["n_positions"]),
        layer_norm_epsilon=float(config["layer_norm_epsilon"]),
        initializer_range=float(config["initializer_range"]),
        hidden_dropout_prob=float(config["resid_pdrop"]),
        attention_probs_dropout_prob=float(config["attn_pdrop"]),
        recompute=False)


def build_model(config: dict):
    from paddle_tpu.models import GPTForCausalLM

    return GPTForCausalLM(program_config(config))


def _fuse_heads(parts, heads):
    """``parts``: arrays ``[..., heads*D]`` -> ``[..., heads * len * D]``
    with each head's slices side by side."""
    lead = parts[0].shape[:-1]
    split = [p.reshape(lead + (heads, -1)) for p in parts]
    return jnp.stack(split, axis=-2).reshape(lead + (-1,))


def _layer(lw: dict, heads: int) -> dict:
    return {
        "ln1.weight": lw["ln_1.g"], "ln1.bias": lw["ln_1.b"],
        "attn.qkv_proj.weight": _fuse_heads(
            [lw["attn.wq"], lw["attn.wk"], lw["attn.wv"]], heads),
        "attn.qkv_proj.bias": _fuse_heads(
            [lw["attn.bq"], lw["attn.bk"], lw["attn.bv"]], heads),
        "attn.out_proj.weight": lw["attn.wo"],
        "attn.out_proj.bias": lw["attn.bo"],
        "ln2.weight": lw["ln_2.g"], "ln2.bias": lw["ln_2.b"],
        "mlp.fc1.weight": lw["mlp.w_fc"], "mlp.fc1.bias": lw["mlp.b_fc"],
        "mlp.fc2.weight": lw["mlp.w_proj"], "mlp.fc2.bias": lw["mlp.b_proj"],
    }


_layer_jit = jax.jit(_layer, static_argnames=("heads",))


def program_leaves(tree: dict, d: dict):
    """Yields ``(state_dict key, array)`` one layer at a time, so that a
    caller can hand each to the model and drop it."""
    from benchmarks.references.gpt import layer_weights

    yield "gpt.embeddings.word_embeddings.weight", tree["wte"]
    yield "gpt.embeddings.position_embeddings.weight", tree["wpe"]
    for i in range(d["layers"]):
        for k, v in _layer_jit(layer_weights(tree, i),
                               heads=d["heads"]).items():
            yield f"gpt.layers.{i}.{k}", v
    yield "gpt.final_ln.weight", tree["ln_f.g"]
    yield "gpt.final_ln.bias", tree["ln_f.b"]

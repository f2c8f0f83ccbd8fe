"""Plain reference of the Llama-shaped decoder that Mistral-7B-v0.3 uses
(Jiang et al. 2023; mistralai/Mistral-7B-v0.3 ``config.json``): rotary
positions (rotate-half, the config's theta), pre-RMSNorm blocks,
grouped-query causal attention, SwiGLU MLP, untied head, no sliding window.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no cache.
It imports nothing of the program and is given seeded weights by the
benchmark.  Serving only: the program has no training path for this family.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.references._common import F32, causal_attention, mm

LAYER_KEYS = ("input_norm.g", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
              "post_norm.g", "mlp.w_gate", "mlp.w_up", "mlp.w_down")


def dims(config: dict) -> dict:
    h, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    return {"hidden": h, "layers": int(config["num_hidden_layers"]),
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or h // heads),
            "ffn": int(config["intermediate_size"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, f, D = d["hidden"], d["ffn"], d["head_dim"]
    out = {"embed": ((d["vocab"], h), "normal"),
           "norm.g": ((h,), "scale"),
           "lm_head": ((h, d["vocab"]), "normal")}
    per_layer = {"input_norm.g": ((h,), "scale"),
                 "attn.wq": ((h, d["heads"] * D), "normal"),
                 "attn.wk": ((h, d["kv_heads"] * D), "normal"),
                 "attn.wv": ((h, d["kv_heads"] * D), "normal"),
                 "attn.wo": ((d["heads"] * D, h), "normal"),
                 "post_norm.g": ((h,), "scale"),
                 "mlp.w_gate": ((h, f), "normal"),
                 "mlp.w_up": ((h, f), "normal"),
                 "mlp.w_down": ((f, h), "normal")}
    for i in range(d["layers"]):
        for k, v in per_layer.items():
            out[f"layers.{i}.{k}"] = v
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta):
    """Rotate-half rotary on ``x [S, H, D]`` at positions 0..S-1."""
    S, _H, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def block(x, lw: dict, *, heads: int, kv_heads: int, head_dim: int,
          eps: float, theta: float, control: bool):
    S, _h = x.shape
    a = _rms(x, lw["input_norm.g"], eps)
    q = mm(a, lw["attn.wq"], control).reshape(S, heads, head_dim)
    k = mm(a, lw["attn.wk"], control).reshape(S, kv_heads, head_dim)
    v = mm(a, lw["attn.wv"], control).reshape(S, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads                       # query head h reads kv h//rep
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    ctx = causal_attention(q, k, v).reshape(S, heads * head_dim)
    x = x + mm(ctx, lw["attn.wo"], control)
    m = _rms(x, lw["post_norm.g"], eps)
    m = jax.nn.silu(mm(m, lw["mlp.w_gate"], control)) \
        * mm(m, lw["mlp.w_up"], control)
    return x + mm(m, lw["mlp.w_down"], control)


EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")


def layer_names(i: int) -> list:
    return [f"layers.{i}.{k}" for k in LAYER_KEYS]


def layer_weights(w: dict, i: int) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in LAYER_KEYS}


@functools.lru_cache(maxsize=None)
def _jit_block(heads, kv_heads, head_dim, eps, theta, control):
    return jax.jit(functools.partial(
        block, heads=heads, kv_heads=kv_heads, head_dim=head_dim, eps=eps,
        theta=theta, control=control))


def hidden_many(provider, seqs, d: dict, *, control: bool = False):
    """Final-block hidden states ``[S, h]`` of each sequence of ``seqs``,
    layer by layer: ``provider(names)`` hands over the named weights (any
    float dtype) when their layer is due, so the whole model is never held;
    each block is one jitted call that upcasts its layer."""
    step = _jit_block(d["heads"], d["kv_heads"], d["head_dim"], d["eps"],
                      d["theta"], control)
    w = provider(EMBED_KEYS)
    xs = [w["embed"][t].astype(F32) for t in seqs]
    for i in range(d["layers"]):
        names = layer_names(i)
        got = provider(names)
        lw = {k: got[n] for k, n in zip(LAYER_KEYS, names)}
        xs = [step(x, lw) for x in xs]
    return xs


def hidden(w: dict, tokens, d: dict, *, control: bool = False):
    """``hidden_many`` of one sequence from a whole tree ``w``."""
    return hidden_many(lambda names: {n: w[n] for n in names}, [tokens], d,
                       control=control)[0]


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control):
    def head(g, lm_head, x):
        return mm(_rms(x, g, eps), lm_head, control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(w["norm.g"], w["lm_head"], x_rows)

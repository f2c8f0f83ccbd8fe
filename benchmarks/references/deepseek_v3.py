"""Plain reference of the DeepSeek-V3-shaped decoder that JoyAI-LLM-Flash uses
(jdopensource/JoyAI-LLM-Flash ``config.json``, ``model_type`` joyai_llm_flash;
the keys are those of DeepSeek-V3, Liu et al. 2024): latent attention written
out per head (no absorption, no cache), interleaved rotary, a dense SwiGLU in
the leading layers and, after them, sigmoid-routed experts with a selection
bias and one shared expert.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no cache.
It imports nothing of the program and is given seeded weights by the
benchmark.  Serving only.

Departures from the published model, each also a line of the configuration's
``assumed``:

- *Held experts.*  The chip holds experts ``held = [start, stop)`` of the
  router's ``experts``.  The router scores all of them, picks ``top_k`` of all
  and normalises over all the chosen, exactly as published; only the held
  experts' terms are added, by a Python loop over them, and what the absent
  ones would add is left out — as in the program, so the partial sum is what
  both hand to the next layer.
- *Depth*: the configuration's ``num_hidden_layers``; *no multi-token
  prediction module*.
- *Seeded weights*: normal(0, 0.02), gains 1 + 0.1 N(0, 1).  The router's
  selection bias (``e_score_correction_bias``, zero at initialisation in the
  published code) is seeded like a gain — a common offset changes no
  selection, so this is 0.1 N(0, 1) — in order that dropping it shows.
- The router is computed in float32 from the float32 hidden state, under
  ``control`` too: a lower-precision deployment keeps its router in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.references._common import F32, mm

ATTN_KEYS = ("input_norm.g", "attn.wq_a", "attn.q_norm.g", "attn.wq_b",
             "attn.wkv_a", "attn.kv_norm.g", "attn.wkv_b", "attn.wo",
             "post_norm.g")
DENSE_KEYS = ATTN_KEYS + ("mlp.w_gate", "mlp.w_up", "mlp.w_down")
MOE_KEYS = ATTN_KEYS + ("moe.router", "moe.bias", "moe.w_gate", "moe.w_up",
                        "moe.w_down", "moe.shared.w_gate", "moe.shared.w_up",
                        "moe.shared.w_down")
#: an expert layer's keys (the leading dense layers have ``DENSE_KEYS``)
LAYER_KEYS = MOE_KEYS
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")
Q_BLOCK = 256            # query rows attended at once (a 6,400-token sequence
#                          then holds 32 x 256 x 6400 float32 scores, 210 MB)


def dims(config: dict) -> dict:
    held = tuple(int(x) for x in config["held_experts"])
    if held[1] - held[0] != int(config["n_routed_experts"]):
        raise ValueError("n_routed_experts is the count of held_experts")
    return {"hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "dense_layers": int(config["first_k_dense_replace"]),
            "heads": int(config["num_attention_heads"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "ffn": int(config["intermediate_size"]),
            "moe_ffn": int(config["moe_intermediate_size"]),
            "experts": int(config["router_experts"]),
            "held": held,
            "top_k": int(config["num_experts_per_tok"]),
            "shared": int(config["n_shared_experts"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def layer_keys(i: int, d: dict) -> tuple:
    return DENSE_KEYS if i < d["dense_layers"] else MOE_KEYS


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H, f, mf = d["hidden"], d["heads"], d["ffn"], d["moe_ffn"]
    G = d["held"][1] - d["held"][0]
    out = {"embed": ((d["vocab"], h), "normal"),
           "norm.g": ((h,), "scale"),
           "lm_head": ((h, d["vocab"]), "normal")}
    shapes = {
        "input_norm.g": ((h,), "scale"),
        "attn.wq_a": ((h, d["q_rank"]), "normal"),
        "attn.q_norm.g": ((d["q_rank"],), "scale"),
        "attn.wq_b": ((d["q_rank"], H * (d["nope"] + d["rope"])), "normal"),
        "attn.wkv_a": ((h, d["kv_rank"] + d["rope"]), "normal"),
        "attn.kv_norm.g": ((d["kv_rank"],), "scale"),
        # per head [k_nope | v], as the published kv_b_proj
        "attn.wkv_b": ((d["kv_rank"], H * (d["nope"] + d["v"])), "normal"),
        "attn.wo": ((H * d["v"], h), "normal"),
        "post_norm.g": ((h,), "scale"),
        "mlp.w_gate": ((h, f), "normal"), "mlp.w_up": ((h, f), "normal"),
        "mlp.w_down": ((f, h), "normal"),
        "moe.router": ((h, d["experts"]), "normal"),
        "moe.bias": ((d["experts"],), "scale"),
        "moe.w_gate": ((G, h, mf), "normal"),       # the held experts only
        "moe.w_up": ((G, h, mf), "normal"),
        "moe.w_down": ((G, mf, h), "normal"),
        "moe.shared.w_gate": ((h, mf * d["shared"]), "normal"),
        "moe.shared.w_up": ((h, mf * d["shared"]), "normal"),
        "moe.shared.w_down": ((mf * d["shared"], h), "normal"),
    }
    for i in range(d["layers"]):
        for k in layer_keys(i, d):
            out[f"layers.{i}.{k}"] = shapes[k]
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope_interleaved(x, theta):
    """``x [S, H, D]`` at positions 0..S-1: the pairs ``(2i, 2i+1)`` are
    de-interleaved to halves, then rotate-half."""
    S, H, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x = x.reshape(S, H, D // 2, 2).transpose(0, 1, 3, 2).reshape(S, H, D)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _blocked_causal_attention(q, k, v):
    """``q``/``k [S, H, Dqk]``, ``v [S, H, Dv]`` -> ``[S, H, Dv]``; a block
    of query rows at a time, softmax in float32 over the causal window."""
    S, _H, D = q.shape
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S
    kpos = jnp.arange(S)
    out = []
    for start in range(0, S, qb):
        s = jnp.einsum("qhd,khd->hqk", q[start:start + qb], k,
                       precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(F32(D))
        mask = kpos[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v,
                              precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(out, axis=0)


def attention(a, lw: dict, d: dict, control: bool):
    S = a.shape[0]
    H, nope, rope, dv, rank = (d["heads"], d["nope"], d["rope"], d["v"],
                               d["kv_rank"])
    c_q = _rms(mm(a, lw["attn.wq_a"], control), lw["attn.q_norm.g"], d["eps"])
    q = mm(c_q, lw["attn.wq_b"], control).reshape(S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope_interleaved(q[..., nope:],
                                                      d["theta"])
    ckr = mm(a, lw["attn.wkv_a"], control)
    c_kv = _rms(ckr[:, :rank], lw["attn.kv_norm.g"], d["eps"])
    k_rope = _rope_interleaved(ckr[:, None, rank:], d["theta"])  # one head
    kv = mm(c_kv, lw["attn.wkv_b"], control).reshape(S, H, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (S, H, rope))], axis=-1)
    ctx = _blocked_causal_attention(
        jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., nope:])
    return mm(ctx.reshape(S, H * dv), lw["attn.wo"], control)


def _swiglu(m, w_gate, w_up, w_down, control):
    return mm(jax.nn.silu(mm(m, w_gate, control)) * mm(m, w_up, control),
              w_down, control)


def route(m, router, bias, d: dict):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's experts."""
    s = jax.nn.sigmoid(mm(m, router, False))
    _, chosen = jax.lax.top_k(s + bias.astype(F32)[None, :], d["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) \
        * d["route_scale"]


def experts(m, lw: dict, d: dict, control: bool, held=None):
    """The routed part the experts ``held`` give (default: the chip's own),
    without the shared expert."""
    start, stop = d["held"] if held is None else held
    chosen, w = route(m, lw["moe.router"], lw["moe.bias"], d)
    y = jnp.zeros_like(m)
    for e in range(start, stop):                    # the held experts only
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        g = e - d["held"][0]                        # its place in the stack
        y = y + w_e[:, None] * _swiglu(m, lw["moe.w_gate"][g],
                                       lw["moe.w_up"][g],
                                       lw["moe.w_down"][g], control)
    return y


def block(x, lw: dict, *, d_items: tuple, moe: bool, control: bool):
    d = dict(d_items)
    x = x + attention(_rms(x, lw["input_norm.g"], d["eps"]), lw, d, control)
    m = _rms(x, lw["post_norm.g"], d["eps"])
    if not moe:
        return x + _swiglu(m, lw["mlp.w_gate"], lw["mlp.w_up"],
                           lw["mlp.w_down"], control)
    return x + experts(m, lw, d, control) + _swiglu(
        m, lw["moe.shared.w_gate"], lw["moe.shared.w_up"],
        lw["moe.shared.w_down"], control)


def layer_names(i: int, d: dict = None) -> list:
    """Layer ``i``'s leaves; without ``d`` an expert layer's."""
    keys = LAYER_KEYS if d is None else layer_keys(i, d)
    return [f"layers.{i}.{k}" for k in keys]


def layer_weights(w: dict, i: int, d: dict) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in layer_keys(i, d)}


@functools.lru_cache(maxsize=None)
def _jit_block(d_items, moe, control):
    return jax.jit(functools.partial(block, d_items=d_items, moe=moe,
                                     control=control))


def hidden_many(provider, seqs, d: dict, *, control: bool = False):
    """Final-block hidden states ``[S, h]`` of each sequence of ``seqs``,
    layer by layer: ``provider(names)`` hands over the named weights (any
    float dtype) when their layer is due, so the whole model is never held."""
    d_items = tuple(sorted(d.items()))
    w = provider(EMBED_KEYS)
    xs = [w["embed"][t].astype(F32) for t in seqs]
    for i in range(d["layers"]):
        keys, names = layer_keys(i, d), layer_names(i, d)
        got = provider(names)
        lw = {k: got[n] for k, n in zip(keys, names)}
        step = _jit_block(d_items, i >= d["dense_layers"], control)
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

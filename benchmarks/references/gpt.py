"""Plain reference of the GPT-2 architecture (Radford et al. 2019, as in
openai-community/gpt2-medium): learned positions, pre-LayerNorm blocks,
multi-head causal attention, tanh-GELU MLP, head tied to the embedding.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no cache,
no batching tricks.  It imports nothing of the program and is given seeded
weights by the benchmark (``harness/weights.py``), never the program's.
Departures from the published model: none in the forward; the training loss
takes the labels it is handed (the benchmark's job feeds seeded labels and
does not shift).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.references._common import F32, causal_attention, mm

LAYER_KEYS = ("ln_1.g", "ln_1.b", "attn.wq", "attn.wk", "attn.wv",
              "attn.bq", "attn.bk", "attn.bv", "attn.wo", "attn.bo",
              "ln_2.g", "ln_2.b", "mlp.w_fc", "mlp.b_fc", "mlp.w_proj",
              "mlp.b_proj")


def dims(config: dict) -> dict:
    h = int(config["n_embd"])
    return {"hidden": h, "layers": int(config["n_layer"]),
            "heads": int(config["n_head"]), "kv_heads": int(config["n_head"]),
            "head_dim": h // int(config["n_head"]), "ffn": 4 * h,
            "vocab": int(config["vocab_size"]),
            "positions": int(config["n_positions"]),
            "eps": float(config["layer_norm_epsilon"])}


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, f = d["hidden"], d["ffn"]
    out = {"wte": ((d["vocab"], h), "normal"),
           "wpe": ((d["positions"], h), "normal"),
           "ln_f.g": ((h,), "scale"), "ln_f.b": ((h,), "normal")}
    per_layer = {"ln_1.g": ((h,), "scale"), "ln_1.b": ((h,), "normal"),
                 "attn.wq": ((h, h), "normal"), "attn.wk": ((h, h), "normal"),
                 "attn.wv": ((h, h), "normal"), "attn.bq": ((h,), "normal"),
                 "attn.bk": ((h,), "normal"), "attn.bv": ((h,), "normal"),
                 "attn.wo": ((h, h), "normal"), "attn.bo": ((h,), "normal"),
                 "ln_2.g": ((h,), "scale"), "ln_2.b": ((h,), "normal"),
                 "mlp.w_fc": ((h, f), "normal"), "mlp.b_fc": ((f,), "normal"),
                 "mlp.w_proj": ((f, h), "normal"),
                 "mlp.b_proj": ((h,), "normal")}
    for i in range(d["layers"]):
        for k, v in per_layer.items():
            out[f"h.{i}.{k}"] = v
    return out


def _ln(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, lw: dict, *, heads: int, eps: float, control: bool):
    """One decoder block on one sequence ``x [S, h]``; ``lw`` holds the
    layer's weights under ``LAYER_KEYS`` (any float dtype, upcast here)."""
    S, h = x.shape
    D = h // heads
    a = _ln(x, lw["ln_1.g"], lw["ln_1.b"], eps)
    q = mm(a, lw["attn.wq"], control) + lw["attn.bq"].astype(F32)
    k = mm(a, lw["attn.wk"], control) + lw["attn.bk"].astype(F32)
    v = mm(a, lw["attn.wv"], control) + lw["attn.bv"].astype(F32)
    ctx = causal_attention(q.reshape(S, heads, D), k.reshape(S, heads, D),
                           v.reshape(S, heads, D)).reshape(S, h)
    x = x + mm(ctx, lw["attn.wo"], control) + lw["attn.bo"].astype(F32)
    m = _ln(x, lw["ln_2.g"], lw["ln_2.b"], eps)
    m = _gelu_tanh(mm(m, lw["mlp.w_fc"], control) + lw["mlp.b_fc"].astype(F32))
    return x + mm(m, lw["mlp.w_proj"], control) + lw["mlp.b_proj"].astype(F32)


EMBED_KEYS = ("wte", "wpe")
HEAD_KEYS = ("ln_f.g", "ln_f.b", "wte")


def layer_names(i: int) -> list:
    return [f"h.{i}.{k}" for k in LAYER_KEYS]


def layer_weights(w: dict, i: int) -> dict:
    return {k: w[f"h.{i}.{k}"] for k in LAYER_KEYS}


def embed(w: dict, tokens):
    S = tokens.shape[0]
    return w["wte"][tokens].astype(F32) + w["wpe"][:S].astype(F32)


def head(w: dict, x, *, eps: float, control: bool):
    x = _ln(x, w["ln_f.g"], w["ln_f.b"], eps)
    return mm(x, w["wte"].T, control)


def hidden_many(provider, seqs, d: dict, *, control: bool = False):
    """Final-block hidden states ``[S, h]`` of each sequence of ``seqs``,
    layer by layer: ``provider(names)`` hands over the named weights (any
    float dtype) when their layer is due, so the whole model is never held;
    each block is one jitted call that upcasts its layer."""
    step = _jit_block(d["heads"], d["eps"], control)
    w = provider(EMBED_KEYS)
    xs = [_jit_embed(w, t) for t in seqs]
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


_jit_embed = jax.jit(embed)


@functools.lru_cache(maxsize=None)
def _jit_block(heads, eps, control):
    return jax.jit(functools.partial(block, heads=heads, eps=eps,
                                     control=control))


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control):
    return jax.jit(functools.partial(head, eps=eps, control=control))


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(
        {k: w[k] for k in ("ln_f.g", "ln_f.b", "wte")}, x_rows)


# -- training -----------------------------------------------------------------

def loss_rows(w: dict, x, y, d: dict, control: bool = False):
    """Summed token cross-entropy of rows ``x``/``y [R, S]`` (float32)."""
    blk = jax.checkpoint(functools.partial(
        block, heads=d["heads"], eps=d["eps"], control=control))

    def one(tokens, labels):
        a = embed(w, tokens)
        for i in range(d["layers"]):
            a = blk(a, layer_weights(w, i))
        lg = head(w, a, eps=d["eps"], control=control)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(
            lg, labels[:, None], axis=-1)[:, 0])

    return jnp.sum(jax.vmap(one)(x, y))


# Layer by layer, so that each compiled piece is one block (the same program
# for every layer): the whole model's backward as one program took minutes to
# compile on the chip and most of the reference's time (PR 23).

@functools.lru_cache(maxsize=None)
def _jit_rows(heads, eps, control):
    blk = functools.partial(block, heads=heads, eps=eps, control=control)
    rows = jax.vmap(blk, in_axes=(0, None))

    def back(x, lw, gy):
        _y, vjp = jax.vjp(rows, x, lw)
        return vjp(gy)

    def top(hw, a, labels):
        def total(hw, a):
            lg = jax.vmap(lambda r: head(hw, r, eps=eps, control=control))(a)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(
                lg, labels[..., None], axis=-1)[..., 0])
        return jax.value_and_grad(total, argnums=(0, 1))(hw, a)

    def bottom(ew, tokens, ga):
        _a, vjp = jax.vjp(lambda ew: jax.vmap(lambda t: embed(ew, t))(tokens),
                          ew)
        return vjp(ga)[0]

    return (jax.jit(jax.vmap(embed, in_axes=(None, 0))), jax.jit(rows),
            jax.jit(back), jax.jit(top), jax.jit(bottom))


def grad_rows(w: dict, x, y, d: dict, control: bool = False):
    """``(summed token cross-entropy, its gradient by weight)`` of rows
    ``x``/``y [R, S]``: forward keeping each block's input, the head and the
    loss, then each block's vector-Jacobian product in reverse.  Equals
    ``jax.value_and_grad(loss_rows)``."""
    emb, fwd, back, top, bottom = _jit_rows(d["heads"], d["eps"], control)
    ew = {k: w[k] for k in EMBED_KEYS}
    hw = {k: w[k] for k in HEAD_KEYS}
    acts = [emb(ew, x)]
    for i in range(d["layers"]):
        acts.append(fwd(acts[-1], layer_weights(w, i)))
    loss, (g_head, ga) = top(hw, acts.pop(), y)
    grads = {}
    for i in reversed(range(d["layers"])):
        ga, glw = back(acts.pop(), layer_weights(w, i), ga)
        for k in LAYER_KEYS:
            grads[f"h.{i}.{k}"] = glw[k]
    g_embed = bottom(ew, x, ga)
    grads["wte"] = g_head["wte"] + g_embed["wte"]
    grads["wpe"] = g_embed["wpe"]
    grads["ln_f.g"], grads["ln_f.b"] = g_head["ln_f.g"], g_head["ln_f.b"]
    return loss, grads

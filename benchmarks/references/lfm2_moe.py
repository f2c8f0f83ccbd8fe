"""Plain reference of LFM2-24B-A2B (LiquidAI/LFM2-24B-A2B ``config.json``,
``model_type`` lfm2_moe): a stack whose layers mix the sequence with a gated
short convolution, three to one with grouped-query attention, two leading
dense SwiGLU layers and then sigmoid-routed experts with a selection bias and
no shared one; the head tied to the embedding.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no cache, no
batching.  It imports nothing of the program and is given seeded weights by
the benchmark.  Serving only.

The layer (``x`` the float32 residual stream; pre-norm):

    h  = x + Op(RMSNorm(x))          Op = Conv or Attn by layer_types[l]
    x' = h + FF(RMSNorm(h))          FF = SwiGLU for l < num_dense_layers,
                                          else the expert layer

- **Conv**: ``[B | C | u] = n W_in`` (three ``hidden``-wide parts in that
  order); ``z = B * u``; ``c[t] = w0 * z[t-2] + w1 * z[t-1] + w2 * z[t]``
  (depthwise, causal, one 3-tap filter a channel, no bias): the three-term
  sum over the sequence padded with zeros in front; ``y = (C * c) W_out``.
- **Attn**: ``q = n W_q -> [H, D]``, ``k``, ``v -> [Hkv, D]``, no biases;
  ``q``, ``k`` through an RMSNorm over each head's ``D``; rotate-half rotary
  at the token's position with ``f_i = theta^(-2i/D)``; scores ``q_t . k_s /
  sqrt(D)`` over ``s <= t``; one softmax; ``o = sum p v``, then ``W_o``.
- **Experts**: ``s = sigmoid(m W_r)`` in float32; the top ``k`` of ``s + b``
  (``expert_bias``: the choice only); weights ``s[chosen] / (sum s[chosen] +
  1e-6) * routed_scaling_factor``; ``y = sum w_e SwiGLU_e(m)``.
- final RMSNorm (no unit offset, like every norm here), logits ``x E^T``.

Departures from the published model, each also a line of the configuration's
``assumed``: ``head_dim = hidden / heads`` and the tied head (the config has
no key for either), *held experts* (only the experts ``held = [start, stop)``
add their terms; the router scores all and normalises over all the chosen),
and *seeded weights* (normal(0, 0.02), the selection bias too; gains and the
filter's taps 1 + 0.1 N(0, 1)).  The router is float32 under ``control`` too.

**How a long sequence fits.**  Attention takes its queries ``Q_BLOCK`` rows
at a time (a block holds ``H x Q_BLOCK x S`` float32 scores) and the dense
layers their rows ``ROW_BLOCK`` at a time.  Sequences that open with the same
tokens share them (``hidden_many``): the common opening goes through each
layer once, and each sequence's own remainder is computed behind what the
opening left — its keys and values, or the last two columns of its ``z`` in
the place of the zeros — the same numbers as a forward of the whole sequence,
since the model is causal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._common import F32, mm

NORM_KEYS = ("operator_norm.g", "ffn_norm.g")
CONV_KEYS = ("conv.w_in", "conv.filter", "conv.w_out")
ATTN_KEYS = ("attn.wq", "attn.wk", "attn.wv", "attn.wo", "attn.q_norm.g",
             "attn.k_norm.g")
DENSE_KEYS = ("mlp.w_gate", "mlp.w_up", "mlp.w_down")
MOE_KEYS = ("moe.router", "moe.bias", "moe.w_gate", "moe.w_up", "moe.w_down")
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "embed")          # the head is the embedding
Q_BLOCK = 64             # query rows attended at once
ROW_BLOCK = 512          # rows of a dense layer at once
SHARE_FROM = 1024        # a common opening shorter than this is not shared
HI = jax.lax.Precision.HIGHEST
CONV, ATTENTION = "conv", "full_attention"


def dims(config: dict) -> dict:
    held = tuple(int(x) for x in config["held_experts"])
    if held[1] - held[0] != int(config["num_experts"]):
        raise ValueError("num_experts is the count of held_experts")
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) \
            or set(kinds) - {CONV, ATTENTION}:
        raise ValueError("layer_types names a kind for every layer")
    rp = config["rope_parameters"]
    if rp["rope_type"] != "default" or config["conv_bias"] \
            or not config["norm_topk_prob"] or not config["use_expert_bias"]:
        raise ValueError("plain rotary, no conv bias, normalised top-k "
                         "weights and a selection bias are what is built")
    return {"hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "dense_layers": int(config["num_dense_layers"]),
            "kinds": kinds,
            "attn_layers": kinds.count(ATTENTION),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "taps": int(config["conv_L_cache"]),
            "ffn": int(config["intermediate_size"]),
            "moe_ffn": int(config["moe_intermediate_size"]),
            "experts": int(config["router_experts"]),
            "held": held,
            "top_k": int(config["num_experts_per_tok"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["norm_eps"]),
            "theta": float(rp["rope_theta"])}


def layer_keys(i: int, d: dict) -> tuple:
    """The leaves of layer ``i``: its kind of operator, its kind of
    feed-forward."""
    return NORM_KEYS + (CONV_KEYS if d["kinds"][i] == CONV else ATTN_KEYS) \
        + (DENSE_KEYS if i < d["dense_layers"] else MOE_KEYS)


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H, Hkv, D = d["hidden"], d["heads"], d["kv_heads"], d["head_dim"]
    f, mf = d["ffn"], d["moe_ffn"]
    G = d["held"][1] - d["held"][0]
    out = {"embed": ((d["vocab"], h), "normal"), "norm.g": ((h,), "scale")}
    shapes = {
        "operator_norm.g": ((h,), "scale"), "ffn_norm.g": ((h,), "scale"),
        "conv.w_in": ((h, 3 * h), "normal"),
        # a tap has a fan-in of 3, not of ``hidden``: at the matrices' 0.02
        # the operator would add a twentieth of what the other layers add
        "conv.filter": ((d["taps"], h), "scale"),
        "conv.w_out": ((h, h), "normal"),
        "attn.wq": ((h, H * D), "normal"),
        "attn.wk": ((h, Hkv * D), "normal"),
        "attn.wv": ((h, Hkv * D), "normal"),
        "attn.wo": ((H * D, h), "normal"),
        "attn.q_norm.g": ((D,), "scale"), "attn.k_norm.g": ((D,), "scale"),
        "mlp.w_gate": ((h, f), "normal"), "mlp.w_up": ((h, f), "normal"),
        "mlp.w_down": ((f, h), "normal"),
        "moe.router": ((h, d["experts"]), "normal"),
        # small and not zero, so that the choice and the weights differ
        "moe.bias": ((d["experts"],), "normal"),
        "moe.w_gate": ((G, h, mf), "normal"),       # the held experts only
        "moe.w_up": ((G, h, mf), "normal"),
        "moe.w_down": ((G, mf, h), "normal"),
    }
    for i in range(d["layers"]):
        for k in layer_keys(i, d):
            out[f"layers.{i}.{k}"] = shapes[k]
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, pos, theta: float):
    """Rotate-half rotary of ``x [S, heads, D]`` at ``pos [S]``."""
    D = x.shape[-1]
    freqs = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = pos.astype(F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _attend_block(qb, qpos, k, v):
    """A block of queries ``qb [n, H, D]`` at positions ``qpos [n]`` over
    keys ``k``/``v [T, Hkv, D]`` at positions ``0 ..``."""
    n, H, D = qb.shape
    Hkv = k.shape[1]
    keep = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    s = jnp.einsum("qgrd,kgd->grqk", qb.reshape(n, Hkv, H // Hkv, D), k,
                   precision=HI) / jnp.sqrt(F32(D))
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI
                      ).reshape(n, H * D)


def _blocked_attention(q, k, v, offset: int):
    """Queries at positions ``offset ..`` over keys at ``0 ..``, ``Q_BLOCK``
    rows at a time."""
    n = q.shape[0]
    qb = Q_BLOCK if n % Q_BLOCK == 0 else n
    pos = (offset + jnp.arange(n)).reshape(n // qb, qb)
    qs = q.reshape(n // qb, qb, *q.shape[1:])
    return jax.lax.map(lambda a: _attend_block(a[0], a[1], k, v),
                       (qs, pos)).reshape(n, -1)


def _swiglu(m, w_gate, w_up, w_down, control):
    return mm(jax.nn.silu(mm(m, w_gate, control)) * mm(m, w_up, control),
              w_down, control)


def _by_rows(f, m):
    """``f`` over ``m [n, h]``, ``ROW_BLOCK`` rows at a time where that
    divides ``n`` (a dense layer's ``[n, 11776]`` need not be held whole)."""
    n = m.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return f(m)
    return jax.lax.map(f, m.reshape(n // ROW_BLOCK, ROW_BLOCK, -1)
                       ).reshape(n, -1)


def conv_columns(n, lw: dict, control: bool):
    """``(z, C)`` of the normed rows ``n [S, h]``: the gated product the
    filter runs over, and the gate of its output."""
    h = n.shape[-1]
    bcu = mm(n, lw["conv.w_in"], control)
    return bcu[:, :h] * bcu[:, 2 * h:], bcu[:, h:2 * h]


def short_conv(z, before, filt):
    """``c[t] = sum_k filt[k] * z[t - (L - 1) + k]`` over ``z [S, h]`` with
    the ``L - 1`` columns ``before`` in front (zeros: the sequence's start):
    the sum of ``L`` shifted products."""
    S, taps = z.shape[0], filt.shape[0]
    ext = jnp.concatenate([before, z], axis=0)
    return sum(filt[k].astype(F32)[None, :] * ext[k:k + S]
               for k in range(taps))


def route(m, router, bias, d: dict):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's experts."""
    s = jax.nn.sigmoid(mm(m, router, False))
    _, chosen = jax.lax.top_k(s + bias.astype(F32)[None, :], d["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6) \
        * d["route_scale"]


def experts(m, lw: dict, d: dict, control: bool, held=None):
    """The part of the layer's output the experts ``held`` give (default:
    the chip's own); ``lw``'s stacks start at ``d["held"][0]``."""
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


def block(x, past, lw: dict, *, d_items: tuple, control: bool, index: int):
    """Layer ``index`` over ``x [n, h]``, the tokens behind ``past``: of an
    attention layer ``(k, v)`` of the tokens before them (empty arrays:
    none), of a convolution layer ``(z_before [L - 1, h],)`` (zeros: none).
    Returns ``(x', past of these tokens' successors)``."""
    d = dict(d_items)
    n = x.shape[0]
    a = _rms(x, lw["operator_norm.g"], d["eps"])
    if d["kinds"][index] == CONV:
        z, gate = conv_columns(a, lw, control)
        c = short_conv(z, past[0], lw["conv.filter"])
        x = x + mm(gate * c, lw["conv.w_out"], control)
        past = (jnp.concatenate([past[0], z], axis=0)[-(d["taps"] - 1):],)
    else:
        H, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
        offset = past[0].shape[0]
        pos = offset + jnp.arange(n)
        q = _rms(mm(a, lw["attn.wq"], control).reshape(n, H, D),
                 lw["attn.q_norm.g"], d["eps"])
        k = _rms(mm(a, lw["attn.wk"], control).reshape(n, Hkv, D),
                 lw["attn.k_norm.g"], d["eps"])
        v = mm(a, lw["attn.wv"], control).reshape(n, Hkv, D)
        q, k = _rope(q, pos, d["theta"]), _rope(k, pos, d["theta"])
        k, v = (jnp.concatenate([p, new], axis=0)
                for p, new in zip(past, (k, v)))
        x = x + mm(_blocked_attention(q, k, v, offset), lw["attn.wo"],
                   control)
        past = (k, v)
    mid = _rms(x, lw["ffn_norm.g"], d["eps"])
    if index < d["dense_layers"]:
        ff = _by_rows(lambda m: _swiglu(m, lw["mlp.w_gate"], lw["mlp.w_up"],
                                        lw["mlp.w_down"], control), mid)
    else:
        ff = experts(mid, lw, d, control)
    return x + ff, past


def layer_names(i: int, d: dict) -> list:
    return [f"layers.{i}.{k}" for k in layer_keys(i, d)]


def layer_weights(w: dict, i: int, d: dict) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in layer_keys(i, d)}


@functools.lru_cache(maxsize=None)
def _jit_block(d_items, control, index):
    return jax.jit(functools.partial(block, d_items=d_items, control=control,
                                     index=index))


def _kind_index(i: int, d: dict) -> int:
    """The first layer with layer ``i``'s kind of operator and of
    feed-forward: layers alike share one compiled ``block``."""
    alike = (d["kinds"][i], i < d["dense_layers"])
    return next(j for j in range(d["layers"])
                if (d["kinds"][j], j < d["dense_layers"]) == alike)


def _no_past(i: int, d: dict):
    if d["kinds"][i] == CONV:
        return (jnp.zeros((d["taps"] - 1, d["hidden"]), F32),)
    return (jnp.zeros((0, d["kv_heads"], d["head_dim"]), F32),) * 2


def shared_openings(seqs) -> list:
    """``[(length, members)]``: the sequences grouped by a common opening of
    ``length`` tokens (whole ``Q_BLOCK``s, ``SHARE_FROM`` or more, leaving
    every member a remainder), each sequence in one group; a sequence that
    shares with none stands alone with length 0."""
    arrs = [np.asarray(s) for s in seqs]
    groups = []
    for i, a in enumerate(arrs):
        for g in groups:
            b = arrs[g["members"][0]]
            m = min(len(a), len(b))
            diff = np.flatnonzero(a[:m] != b[:m])
            common = int(diff[0]) if len(diff) else m
            common = min(common, g["length"] or common, m - 1)
            common -= common % Q_BLOCK
            if common >= SHARE_FROM:
                g["members"].append(i)
                g["length"] = common
                break
        else:
            groups.append({"members": [i], "length": 0})
    return [(g["length"] if len(g["members"]) > 1 else 0, g["members"])
            for g in groups]


def hidden_many(provider, seqs, d: dict, *, control: bool = False):
    """Final-block hidden states ``[S, h]`` of each sequence of ``seqs``,
    layer by layer: ``provider(names)`` hands over the named weights (any
    float dtype) when their layer is due, so the whole model is never held.
    A common opening of several sequences goes through each layer once."""
    d_items = tuple(sorted(d.items()))
    emb = provider(EMBED_KEYS)["embed"]
    groups = []
    for length, members in shared_openings(seqs):
        first = jnp.asarray(seqs[members[0]])
        groups.append({
            "members": members,
            "open": emb[first[:length]].astype(F32) if length else None,
            "rest": [emb[jnp.asarray(seqs[m])[length:]].astype(F32)
                     for m in members]})
    del emb
    for i in range(d["layers"]):
        step = _jit_block(d_items, control, _kind_index(i, d))
        names = layer_names(i, d)
        got = provider(names)
        lw = {k: got[n] for k, n in zip(layer_keys(i, d), names)}
        for g in groups:
            past = _no_past(i, d)
            if g["open"] is not None:
                g["open"], past = step(g["open"], past, lw)
            g["rest"] = [step(x, past, lw)[0] for x in g["rest"]]
            del past
    out = [None] * len(seqs)
    for g in groups:
        for m, x in zip(g["members"], g["rest"]):
            out[m] = x if g["open"] is None \
                else jnp.concatenate([g["open"], x], axis=0)
    return out


def hidden(w: dict, tokens, d: dict, *, control: bool = False):
    """``hidden_many`` of one sequence from a whole tree ``w``."""
    return hidden_many(lambda names: {n: w[n] for n in names}, [tokens], d,
                       control=control)[0]


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control):
    def head(g, embed, x):
        return mm(_rms(x, g, eps), embed.T, control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(w["norm.g"], w["embed"], x_rows)

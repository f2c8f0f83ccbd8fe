"""Plain reference of Mellum2-12B-A2.5B (JetBrains/Mellum2-12B-A2.5B-Instruct
``config.json``, ``model_type`` mellum): grouped-query attention with per-head
q/k RMSNorm whose layers are of two kinds — ``sliding_attention`` over a
window behind the query under the plain rotary, ``full_attention`` over the
whole sequence under a YaRN-scaled rotary — and softmax-routed experts with
no shared one in every layer.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no paged
cache, no batching.  It imports nothing of the program and is given seeded
weights by the benchmark.  Serving only.

The layer (``x`` the float32 residual stream, ``a = RMSNorm(x)``):

- ``q = a W_q -> [H, D]``, ``k = a W_k``, ``v = a W_v -> [Hkv, D]``; ``q``,
  ``k`` through an RMSNorm over each head's ``D``; rotate-half rotary at the
  token's position ``t`` with ``D/2`` frequencies ``f_i``, cos and sin times
  ``m``:
  - sliding layer: ``f_i = theta^(-2i/D)``, ``m = 1``;
  - full layer (YaRN, written out from the config's ``rope_parameters``):
    ``c(r) = D ln(L0 / (2 pi r)) / (2 ln theta)`` is the dimension that makes
    ``r`` rotations over the original length ``L0``; ``lo = floor(c(beta_fast))``,
    ``hi = ceil(c(beta_slow))`` (``truncate``), both kept inside ``[0, D -
    1]``; ``g_i = clip((i - lo) / (hi - lo), 0, 1)``;
    ``f_i = theta^(-2i/D) (1 - g_i) + theta^(-2i/D) / factor * g_i``;
    ``m = attention_factor``.
- scores ``q_t . k_s / sqrt(D)``; a full layer keeps ``s <= t``, a sliding
  layer ``t - W < s <= t`` (the window counts the query's own position);
  one softmax; ``o = sum p v``, then ``W_o``.
- experts: ``p = softmax(m W_r)`` over all experts in float32, the top
  ``k``, weights ``p[chosen] / sum p[chosen]``, ``y = sum w_e SwiGLU_e(m)``.

Departures from the published model, each also a line of the configuration's
``assumed``: the per-head q/k RMSNorm (the Qwen3-MoE decoder's convention,
whose key set the config carries), YaRN's ``truncate`` true, ``layer_types``
alone deciding a layer's kind (``max_window_layers`` 0 exempts none), *held
experts* (only the experts ``held = [start, stop)`` add their terms; the
router scores all and normalises over all the chosen), no MTP head, and
*seeded weights* (normal(0, 0.02), gains 1 + 0.1 N(0, 1)).  The router is
float32 under ``control`` too.

**How a long sequence fits.**  Queries are taken ``Q_BLOCK`` rows at a time
(a block of a full layer holds ``H x Q_BLOCK x S`` float32 scores); a block
of a sliding layer is given only the ``W + Q_BLOCK`` keys that can meet its
windows.  Sequences that open with the same tokens share them
(``hidden_many``): the common opening goes through each layer once, and each
sequence's own remainder attends to its keys and values — the same numbers
as a forward of the whole sequence, since the model is causal.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._common import F32, mm

LAYER_KEYS = ("input_norm.g", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
              "attn.q_norm.g", "attn.k_norm.g", "post_norm.g",
              "moe.router", "moe.w_gate", "moe.w_up", "moe.w_down")
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")
Q_BLOCK = 64             # query rows attended at once
SHARE_FROM = 1024        # a common opening shorter than this is not shared
HI = jax.lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"


def dims(config: dict) -> dict:
    held = tuple(int(x) for x in config["held_experts"])
    if held[1] - held[0] != int(config["num_experts"]):
        raise ValueError("num_experts is the count of held_experts")
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) \
            or set(kinds) - {SLIDING, FULL}:
        raise ValueError("layer_types names a kind for every layer")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every layer is sparse")
    rp = config["rope_parameters"]
    yarn, plain = rp[FULL], rp[SLIDING]
    if yarn["rope_type"] != "yarn" or plain["rope_type"] != "default":
        raise ValueError("full layers under yarn, sliding layers plain")
    return {"hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "dense_layers": 0,               # every layer has experts
            "kinds": kinds,
            "full_layers": kinds.count(FULL),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "window": int(config["sliding_window"]),
            "moe_ffn": int(config["moe_intermediate_size"]),
            "experts": int(config["router_experts"]),
            "held": held,
            "top_k": int(config["num_experts_per_tok"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(plain["rope_theta"]),
            "yarn_theta": float(yarn["rope_theta"]),
            "yarn_factor": float(yarn["factor"]),
            "yarn_original": int(yarn["original_max_position_embeddings"]),
            "yarn_beta_fast": float(yarn["beta_fast"]),
            "yarn_beta_slow": float(yarn["beta_slow"]),
            "yarn_attention_factor": float(yarn["attention_factor"])}


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H, Hkv, D = d["hidden"], d["heads"], d["kv_heads"], d["head_dim"]
    mf = d["moe_ffn"]
    G = d["held"][1] - d["held"][0]
    out = {"embed": ((d["vocab"], h), "normal"),
           "norm.g": ((h,), "scale"),
           "lm_head": ((h, d["vocab"]), "normal")}
    shapes = {
        "input_norm.g": ((h,), "scale"),
        "attn.wq": ((h, H * D), "normal"),
        "attn.wk": ((h, Hkv * D), "normal"),
        "attn.wv": ((h, Hkv * D), "normal"),
        "attn.wo": ((H * D, h), "normal"),
        "attn.q_norm.g": ((D,), "scale"),
        "attn.k_norm.g": ((D,), "scale"),
        "post_norm.g": ((h,), "scale"),
        "moe.router": ((h, d["experts"]), "normal"),
        "moe.w_gate": ((G, h, mf), "normal"),       # the held experts only
        "moe.w_up": ((G, h, mf), "normal"),
        "moe.w_down": ((G, mf, h), "normal"),
    }
    for i in range(d["layers"]):
        for k in LAYER_KEYS:
            out[f"layers.{i}.{k}"] = shapes[k]
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def rotary_frequencies(d: dict, kind: str):
    """``(f [D/2] float64, m)`` of a layer of ``kind``, as the module's
    docstring writes them."""
    D = d["head_dim"]
    i = np.arange(D // 2, dtype=np.float64)
    if kind == SLIDING:
        return d["theta"] ** (-2.0 * i / D), 1.0
    theta, L0 = d["yarn_theta"], d["yarn_original"]
    plain = theta ** (-2.0 * i / D)

    def c(r):
        return D * math.log(L0 / (2 * math.pi * r)) / (2 * math.log(theta))

    lo = max(math.floor(c(d["yarn_beta_fast"])), 0)
    hi = min(math.ceil(c(d["yarn_beta_slow"])), D - 1)
    if lo == hi:
        hi += 0.001
    g = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return plain * (1.0 - g) + plain / d["yarn_factor"] * g, \
        d["yarn_attention_factor"]


def _rope(x, pos, freqs, m: float):
    """Rotate-half rotary of ``x [S, heads, D]`` at ``pos [S]``."""
    D = x.shape[-1]
    ang = pos.astype(F32)[:, None] * jnp.asarray(freqs, F32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return (x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1)
            * jnp.sin(ang)) * m


def _attend_block(qb, qpos, k, v, kpos, window: int):
    """A block of queries ``qb [n, H, D]`` at positions ``qpos [n]`` over
    keys ``k``/``v [T, Hkv, D]`` at positions ``kpos [T]``."""
    n, H, D = qb.shape
    Hkv = k.shape[1]
    keep = kpos[None, :] <= qpos[:, None]
    if window:
        keep &= kpos[None, :] > qpos[:, None] - window
    s = jnp.einsum("qgrd,kgd->grqk", qb.reshape(n, Hkv, H // Hkv, D), k,
                   precision=HI) / jnp.sqrt(F32(D))
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI
                      ).reshape(n, H * D)


def _blocked_attention(q, k, v, offset: int, window: int):
    """Queries at positions ``offset ..`` over keys at ``0 ..``; ``Q_BLOCK``
    rows at a time.  With no window a quarter of a long run of queries at a
    time sees only the keys up to its own end; with one a block sees the
    ``window + Q_BLOCK`` keys that can meet its rows' windows."""
    n, T = q.shape[0], k.shape[0]
    qb = Q_BLOCK if n % Q_BLOCK == 0 else n
    blocks = n // qb
    pos = (offset + jnp.arange(n)).reshape(blocks, qb)
    qs = q.reshape(blocks, qb, *q.shape[1:])
    if window:
        span = min(T, window + qb)

        def one(args):
            qb_, pos_ = args
            lo = jnp.clip(pos_[0] - window + 1, 0, T - span)
            return _attend_block(
                qb_, pos_, jax.lax.dynamic_slice_in_dim(k, lo, span),
                jax.lax.dynamic_slice_in_dim(v, lo, span),
                lo + jnp.arange(span), window)

        return jax.lax.map(one, (qs, pos)).reshape(n, -1)
    parts = 4 if n % (4 * qb) == 0 and n >= 4096 else 1
    out = []
    for part in range(parts):
        lo, hi = part * blocks // parts, (part + 1) * blocks // parts
        upto = offset + hi * qb

        def one(args, upto=upto):
            qb_, pos_ = args
            return _attend_block(qb_, pos_, k[:upto], v[:upto],
                                 jnp.arange(upto), 0)

        out.append(jax.lax.map(one, (qs[lo:hi], pos[lo:hi])
                               ).reshape((hi - lo) * qb, -1))
    return jnp.concatenate(out, axis=0)


def _swiglu(m, w_gate, w_up, w_down, control):
    return mm(jax.nn.silu(mm(m, w_gate, control)) * mm(m, w_up, control),
              w_down, control)


def route(m, router, d: dict):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's experts."""
    p = jax.nn.softmax(mm(m, router, False), axis=-1)
    w, chosen = jax.lax.top_k(p, d["top_k"])
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)


def experts(m, lw: dict, d: dict, control: bool, held=None):
    """The part of the layer's output the experts ``held`` give (default:
    the chip's own); ``lw``'s stacks start at ``d["held"][0]``."""
    start, stop = d["held"] if held is None else held
    chosen, w = route(m, lw["moe.router"], d)
    y = jnp.zeros_like(m)
    for e in range(start, stop):                    # the held experts only
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        g = e - d["held"][0]                        # its place in the stack
        y = y + w_e[:, None] * _swiglu(m, lw["moe.w_gate"][g],
                                       lw["moe.w_up"][g],
                                       lw["moe.w_down"][g], control)
    return y


def block(x, past, lw: dict, *, d_items: tuple, control: bool, kind: str,
          window_off: bool = False):
    """One layer of ``kind`` over ``x [n, h]``, the tokens at positions
    ``offset ..`` behind ``past = (k, v)`` of the ``offset`` tokens before
    them (empty arrays: none).  Returns ``(x', (k, v) of past + these)``.
    ``window_off``: the tests' broken path, a sliding layer that attends
    over everything."""
    d = dict(d_items)
    n = x.shape[0]
    H, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    offset = past[0].shape[0]
    pos = offset + jnp.arange(n)
    freqs, m = rotary_frequencies(d, kind)
    a = _rms(x, lw["input_norm.g"], d["eps"])
    q = _rms(mm(a, lw["attn.wq"], control).reshape(n, H, D),
             lw["attn.q_norm.g"], d["eps"])
    k = _rms(mm(a, lw["attn.wk"], control).reshape(n, Hkv, D),
             lw["attn.k_norm.g"], d["eps"])
    v = mm(a, lw["attn.wv"], control).reshape(n, Hkv, D)
    q, k = _rope(q, pos, freqs, m), _rope(k, pos, freqs, m)
    k, v = (jnp.concatenate([p, new], axis=0)
            for p, new in zip(past, (k, v)))
    window = d["window"] if kind == SLIDING and not window_off else 0
    ctx = _blocked_attention(q, k, v, offset, window)
    x = x + mm(ctx, lw["attn.wo"], control)
    mid = _rms(x, lw["post_norm.g"], d["eps"])
    return x + experts(mid, lw, d, control), (k, v)


def layer_names(i: int, d: dict = None) -> list:
    return [f"layers.{i}.{k}" for k in LAYER_KEYS]


def layer_weights(w: dict, i: int, d: dict) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in LAYER_KEYS}


@functools.lru_cache(maxsize=None)
def _jit_block(d_items, control, kind, window_off):
    return jax.jit(functools.partial(block, d_items=d_items, control=control,
                                     kind=kind, window_off=window_off))


def _no_past(d: dict):
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


def hidden_many(provider, seqs, d: dict, *, control: bool = False,
                window_off: bool = False):
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
        step = _jit_block(d_items, control, d["kinds"][i], window_off)
        names = layer_names(i)
        got = provider(names)
        lw = {k: got[n] for k, n in zip(LAYER_KEYS, names)}
        for g in groups:
            past = _no_past(d)
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


def hidden(w: dict, tokens, d: dict, *, control: bool = False,
           window_off: bool = False):
    """``hidden_many`` of one sequence from a whole tree ``w``."""
    return hidden_many(lambda names: {n: w[n] for n in names}, [tokens], d,
                       control=control, window_off=window_off)[0]


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control):
    def head(g, lm_head, x):
        return mm(_rms(x, g, eps), lm_head, control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(w["norm.g"], w["lm_head"], x_rows)

"""Plain reference of the language model of Keye-VL-2.0-30B-A3B
(Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``, ``model_type`` KeyeVL2):
grouped-query attention with per-head q/k RMSNorm and three-stream rotary,
restricted for every query to the tokens a learned indexer selects
(``sa_config``), and softmax-routed experts with no shared one.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no paged
cache, no batching.  It imports nothing of the program and is given seeded
weights by the benchmark.  Serving only; the vision tower is not built.

The layer (``x`` the float32 residual stream, ``a = RMSNorm(x)``):

- ``q = a W_q -> [H, D]``, ``k = a W_k``, ``v = a W_v -> [Hkv, D]``; ``q``,
  ``k`` through an RMSNorm over each head's ``D``; rotate-half rotary with
  ``D/2`` frequencies ``theta^(-2i/D)``, frequency ``i`` on the position
  stream ``mrope_section`` gives it (text: the three streams are equal).
- indexer: ``q^I = a W_qI -> [Hi, Di]``, ``k^I = LayerNorm(a W_kI) -> [Di]``,
  ``w = a W_wI -> [Hi]``; rotate-half rotary over the whole ``Di`` of
  ``q^I`` and ``k^I`` on stream 0; ``I(t, s) = sum_j w_tj Hi^-1/2 Di^-1/2
  relu(q^I_tj . k^I_s)`` for ``s <= t``; ``S_t`` = every ``s <= t`` with
  ``I(t, s)`` at or above the ``topk``-th largest of the row (all of them
  while the row is shorter; ties at the cut all kept).
- ``o = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s``, then ``W_o``.
- experts: ``p = softmax(m W_r)`` over all experts in float32, the top
  ``k``, weights ``p[chosen] / sum p[chosen]``, ``y = sum w_e SwiGLU_e(m)``.

Departures from the published model, each also a line of the configuration's
``assumed``: the per-head q/k RMSNorm (the Qwen3 decoder's convention), the
LayerNorm on the indexer's key, the rotary on the indexer, its two scale
factors and that it reads the same normed ``a`` as attention (the published
lightning indexer of DeepSeek-V3.2-Exp), *held experts* (only the experts
``held = [start, stop)`` add their terms; the router scores all and
normalises over all the chosen), the *depth*, and *seeded weights*
(normal(0, 0.02), gains 1 + 0.1 N(0, 1); the LayerNorm's bias normal(0,
0.02) so that dropping it shows).  The router is float32 under ``control``
too.

**How a long sequence fits.**  Queries are taken ``Q_BLOCK`` rows at a time
(a block holds ``H x Q_BLOCK x S`` float32 scores), and the ``topk``-th
largest index score of a row is found by 32 counting passes over the row's
order keys (the largest value that ``topk`` or more entries reach; checked
against ``numpy.sort`` in ``tests/benchmark_tests``), because a sort of
31k x 31k scores a layer is most of a minute on the chip.  Sequences that
open with the same tokens share them (``hidden_many``): the common opening
goes through each layer once, and each sequence's own remainder attends to
its keys, values and indexer keys — the same numbers as a forward of the
whole sequence, since the model is causal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._common import F32, mm

LAYER_KEYS = ("input_norm.g", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
              "attn.q_norm.g", "attn.k_norm.g", "idx.wq", "idx.wk",
              "idx.k_norm.g", "idx.k_norm.b", "idx.ww", "post_norm.g",
              "moe.router", "moe.w_gate", "moe.w_up", "moe.w_down")
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")
Q_BLOCK = 64             # query rows attended at once (32 x 64 x 31k float32
#                          scores are 250 MB, beside a program that holds
#                          two thirds of the chip)
SHARE_FROM = 1024        # a common opening shorter than this is not shared
HI = jax.lax.Precision.HIGHEST


def dims(config: dict) -> dict:
    held = tuple(int(x) for x in config["held_experts"])
    if held[1] - held[0] != int(config["num_experts"]):
        raise ValueError("num_experts is the count of held_experts")
    sa = config["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("one indexer key a token")
    return {"hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "dense_layers": 0,               # every layer has experts
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "idx_heads": int(sa["indexer_num_heads"]),
            "idx_dim": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"]),
            "sections": tuple(int(x) for x in
                              config["rope_scaling"]["mrope_section"]),
            "moe_ffn": int(config["moe_intermediate_size"]),
            "experts": int(config["router_experts"]),
            "held": held,
            "top_k": int(config["num_experts_per_tok"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H, Hkv, D = d["hidden"], d["heads"], d["kv_heads"], d["head_dim"]
    Hi, Di, mf = d["idx_heads"], d["idx_dim"], d["moe_ffn"]
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
        "idx.wq": ((h, Hi * Di), "normal"),
        "idx.wk": ((h, Di), "normal"),
        "idx.k_norm.g": ((Di,), "scale"),
        "idx.k_norm.b": ((Di,), "normal"),
        "idx.ww": ((h, Hi), "normal"),
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


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _rope(x, pos3, theta, sections=None):
    """Rotate-half rotary of ``x [S, heads, D]`` at ``pos3 [3, S]``:
    frequency ``i`` of the ``D/2`` reads the stream ``sections`` gives it
    (stream 0 for all without)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    stream = np.zeros(D // 2, np.int32) if sections is None \
        else np.repeat(np.arange(3), sections)
    ang = pos3.astype(F32)[stream, :].T * inv[None, :]        # [S, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def kth_largest(z, k: int):
    """Per row of ``z [N, T]`` (float32, ``-inf`` allowed) its ``k``-th
    largest value (the smallest for a row shorter than ``k``): the largest
    ``v`` that ``k`` or more entries reach, built bit by bit on integer keys
    whose order is the floats'."""
    k = min(int(k), z.shape[1])
    b = jax.lax.bitcast_convert_type(jnp.where(z == 0.0, 0.0, z), jnp.uint32)
    keys = jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))

    def bit(i, v):
        up = v | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        reach = jnp.sum(keys >= up[:, None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(reach, up, v)

    v = jax.lax.fori_loop(0, 32, bit, jnp.zeros(z.shape[:1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(v >> 31 == 1, v ^ jnp.uint32(1 << 31), ~v), F32)


def _attend_block(qb, qib, wb, qpos, k, v, ki, d: dict, select):
    """A block of queries ``qb [n, H, D]`` (indexer ``qib [n, Hi, Di]``,
    ``wb [n, Hi]``) at positions ``qpos [n]`` over keys ``k``/``v [T, Hkv,
    D]``, ``ki [T, Di]`` at positions ``0..T-1``."""
    n, H, D = qb.shape
    Hkv, T = k.shape[1], k.shape[0]
    causal = jnp.arange(T)[None, :] <= qpos[:, None]            # [n, T]
    idx = jnp.einsum("qjd,kd->qjk", qib, ki, precision=HI)
    idx = jnp.sum(jnp.maximum(idx, 0.0) * wb[:, :, None], axis=1)
    idx = jnp.where(causal, idx, -jnp.inf)
    keep = causal & select(idx, d["topk"])
    s = jnp.einsum("qgrd,kgd->grqk", qb.reshape(n, Hkv, H // Hkv, D), k,
                   precision=HI) / jnp.sqrt(F32(D))
    p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI
                      ).reshape(n, H * D)


def select_topk(idx, topk: int):
    """The published selection: at or above the row's ``topk``-th largest."""
    return idx >= kth_largest(idx, topk)[:, None]


def select_first(idx, topk: int):
    """A wrong selection (the tests' broken path): the first ``topk``
    positions, whatever their scores."""
    return jnp.broadcast_to(jnp.arange(idx.shape[1])[None, :] < topk,
                            idx.shape)


def _blocked_attention(q, qi, w, k, v, ki, offset: int, d: dict, select):
    """Queries at positions ``offset ..`` over keys at ``0 ..``; ``Q_BLOCK``
    rows at a time, and a quarter of a long run of queries at a time sees
    only the keys up to its own end."""
    n = q.shape[0]
    qb = Q_BLOCK if n % Q_BLOCK == 0 else n
    parts = 4 if n % (4 * qb) == 0 and n >= 4096 else 1
    out = []
    for part in range(parts):
        lo, hi = part * n // parts, (part + 1) * n // parts
        T = offset + hi

        def one(args, T=T):
            qb_, qib_, wb_, pos_ = args
            return _attend_block(qb_, qib_, wb_, pos_, k[:T], v[:T], ki[:T],
                                 d, select)

        blocks = (hi - lo) // qb
        pos = offset + lo + jnp.arange(hi - lo)
        o = jax.lax.map(one, (
            q[lo:hi].reshape(blocks, qb, *q.shape[1:]),
            qi[lo:hi].reshape(blocks, qb, *qi.shape[1:]),
            w[lo:hi].reshape(blocks, qb, -1), pos.reshape(blocks, qb)))
        out.append(o.reshape(hi - lo, -1))
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
    the chip's own)."""
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


def block(x, past, lw: dict, pos3=None, *, d_items: tuple, control: bool,
          select=select_topk):
    """One layer over ``x [n, h]``, the tokens at positions ``offset ..``
    behind ``past = (k, v, ki)`` of the ``offset`` tokens before them (empty
    arrays: none).  Returns ``(x', (k, v, ki) of past + these)``.
    ``pos3 [3, n]``: the three position streams (default: equal)."""
    d = dict(d_items)
    n = x.shape[0]
    H, Hkv, D = d["heads"], d["kv_heads"], d["head_dim"]
    Hi, Di = d["idx_heads"], d["idx_dim"]
    offset = past[0].shape[0]
    if pos3 is None:
        pos3 = jnp.broadcast_to(offset + jnp.arange(n)[None], (3, n))
    a = _rms(x, lw["input_norm.g"], d["eps"])
    q = _rms(mm(a, lw["attn.wq"], control).reshape(n, H, D),
             lw["attn.q_norm.g"], d["eps"])
    k = _rms(mm(a, lw["attn.wk"], control).reshape(n, Hkv, D),
             lw["attn.k_norm.g"], d["eps"])
    v = mm(a, lw["attn.wv"], control).reshape(n, Hkv, D)
    q = _rope(q, pos3, d["theta"], d["sections"])
    k = _rope(k, pos3, d["theta"], d["sections"])
    qi = _rope(mm(a, lw["idx.wq"], control).reshape(n, Hi, Di), pos3,
               d["theta"])
    ki = _rope(_layer_norm(mm(a, lw["idx.wk"], control), lw["idx.k_norm.g"],
                           lw["idx.k_norm.b"], d["eps"])[:, None, :], pos3,
               d["theta"])[:, 0]
    w = mm(a, lw["idx.ww"], control) * (Hi * Di) ** -0.5
    k, v, ki = (jnp.concatenate([p, new], axis=0)
                for p, new in zip(past, (k, v, ki)))
    ctx = _blocked_attention(q, qi, w, k, v, ki, offset, d, select)
    x = x + mm(ctx, lw["attn.wo"], control)
    m = _rms(x, lw["post_norm.g"], d["eps"])
    return x + experts(m, lw, d, control), (k, v, ki)


def layer_names(i: int, d: dict = None) -> list:
    return [f"layers.{i}.{k}" for k in LAYER_KEYS]


def layer_weights(w: dict, i: int, d: dict) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in LAYER_KEYS}


@functools.lru_cache(maxsize=None)
def _jit_block(d_items, control, select):
    return jax.jit(functools.partial(block, d_items=d_items, control=control,
                                     select=select))


def _no_past(d: dict):
    return (jnp.zeros((0, d["kv_heads"], d["head_dim"]), F32),
            jnp.zeros((0, d["kv_heads"], d["head_dim"]), F32),
            jnp.zeros((0, d["idx_dim"]), F32))


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
                select=select_topk):
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
    step = _jit_block(d_items, control, select)
    for i in range(d["layers"]):
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
           select=select_topk):
    """``hidden_many`` of one sequence from a whole tree ``w``."""
    return hidden_many(lambda names: {n: w[n] for n in names}, [tokens], d,
                       control=control, select=select)[0]


def hidden_embeds(w: dict, embeds, pos3, d: dict):
    """Final-block hidden states of input *embeddings* ``[S, h]`` at the
    position streams ``pos3 [3, S]`` (what a vision tower would feed)."""
    d_items = tuple(sorted(d.items()))
    x = jnp.asarray(embeds, F32)
    for i in range(d["layers"]):
        x, _ = block(x, _no_past(d), layer_weights(w, i, d),
                     jnp.asarray(pos3), d_items=d_items, control=False)
    return x


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control):
    def head(g, lm_head, x):
        return mm(_rms(x, g, eps), lm_head, control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(w["norm.g"], w["lm_head"], x_rows)

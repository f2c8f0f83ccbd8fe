"""Plain reference of EvaByte (EvaByte/EvaByte ``config.json``, ``model_type``
evabyte, ``attention_class`` "eva"): a byte-level decoder whose attention
keeps the exact keys and values of the query's own window of ``window_size``
positions and, of every window passed, one learned summary key and value a
chunk of ``chunk_size``.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no paged
cache, no batching.  It imports nothing of the program and is given seeded
weights by the benchmark.  Serving only.

The layer (``x`` the float32 residual stream; ``RMSNorm(x) = x / sqrt(mean(x^2)
+ eps) * (1 + g)``, the config's ``norm_add_unit_offset``):

- ``h = x + Attn(RMSNorm_1(x))``, ``y = h + W_down(silu(W_gate u) * W_up u)``
  with ``u = RMSNorm_2(h)``; no bias anywhere.
- per head (``H x D``, ``s = D^-1/2``): ``q_i = R_i W_q u_i``, ``k_i = R_i W_k
  u_i``, ``v_i = W_v u_i``; ``R`` the rotate-half rotary over all ``D``
  dimensions (``D/2`` frequencies ``theta^(-2j/D)``).
- window of a position ``w(i) = i // W``; chunk ``c = j // C``.  Summary of
  chunk ``c`` by the head's learned ``phi``, ``mu``: ``a_j = softmax_{j in
  c}(s <k_j, phi>)``, ``k~_c = sum_j a_j k_j + mu``, ``v~_c = sum_j a_j v_j``.
- output: ONE softmax over the exact keys ``{j : w(j) = w(i), j <= i}`` with
  logits ``s <q_i, k_j>`` and values ``v_j``, and the summaries ``{c : chunk c
  lies in a window < w(i)}`` with logits ``s <q_i, k~_c>`` and values ``v~_c``;
  then ``W_o``.  A chunk of the query's own window is never read as a
  summary.
- head ``[h, P x V]``, head-major (``num_pred_heads`` predictors); predictor 0
  is the next byte, which is what is served and compared
  (:func:`logits_rows`); :func:`logits_all` gives the ``P``.

Departures from the published model, each also a line of the configuration's
``assumed`` (the config gives ``chunk_size``, ``window_size``,
``attention_class`` and no more about the attention): the pooling weights are
the per-chunk softmax of ``s <k, phi>`` and the summary key carries ``+ mu``;
summaries are taken of *rotated* keys; a summary's logit has no additive term
(no log of the chunk's size); ``head_dim = hidden / heads``; the head's
layout; the *depth*; and *seeded weights*: normal(0, 0.02) matrices, gains
``g = 4 x`` a normal(0, 0.02) leaf (0.08 N(0, 1): exact in bfloat16, and a
dropped gain shows), ``phi`` and ``mu`` ``D^-1/2 clip(N(0, 1), -1, 1)``
rounded to bfloat16 (:func:`pooling_vector`; the program is handed the same
rounded numbers).

**How a long sequence fits.**  A sequence goes through a layer a window's
part at a time (:func:`pieces`, :func:`block`): the layer's state between
parts is the current window's exact keys and values and the summary rows of
the windows passed — what the mathematics keeps, in float32, at fixed shapes,
so a part of ``n`` rows compiles once whatever came before it — and a part's
queries are attended ``Q_BLOCKS`` rows at a time (scores ``H x 512 x (W +
positions / C)`` float32).  Sequences that open with the same tokens share
them (``hidden_many``): the common opening (a document) goes through each
layer once and each sequence's own remainder continues from its state — the
same numbers as a forward of the whole sequence, since the model is causal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._common import F32, mm

LAYER_KEYS = ("input_norm.g", "attn.wq", "attn.wk", "attn.wv", "attn.wo",
              "attn.phi", "attn.mu", "post_norm.g", "mlp.w_gate", "mlp.w_up",
              "mlp.w_down")
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")
Q_BLOCKS = (512, 256, 128, 64)   # query rows attended at once: the largest
#                                  that divides the run of queries
SHARE_FROM = 1024        # a common opening shorter than this is not shared
HI = jax.lax.Precision.HIGHEST
SEED_STD = 0.02          # the benchmark's "normal" leaves


def dims(config: dict) -> dict:
    if config.get("attention_class") != "eva":
        raise ValueError("this reference computes EVA attention")
    hidden, heads = int(config["hidden_size"]), \
        int(config["num_attention_heads"])
    if int(config["num_key_value_heads"]) != heads:
        raise ValueError("one key and value head a query head")
    return {"hidden": hidden,
            "layers": int(config["num_hidden_layers"]),
            "heads": heads, "kv_heads": heads,
            "head_dim": hidden // heads,
            "ffn": int(config["intermediate_size"]),
            "window": int(config["window_size"]),
            "chunk": int(config["chunk_size"]),
            "pred_heads": int(config["num_pred_heads"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["max_position_embeddings"]),
            "eps": float(config["rms_norm_eps"]),
            "theta": float(config["rope_theta"])}


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H, D, f = d["hidden"], d["heads"], d["head_dim"], d["ffn"]
    out = {"embed": ((d["vocab"], h), "normal"),
           "norm.g": ((h,), "normal"),
           "lm_head": ((h, d["pred_heads"] * d["vocab"]), "normal")}
    shapes = {
        "input_norm.g": ((h,), "normal"),
        "attn.wq": ((h, H * D), "normal"),
        "attn.wk": ((h, H * D), "normal"),
        "attn.wv": ((h, H * D), "normal"),
        "attn.wo": ((H * D, h), "normal"),
        "attn.phi": ((H, D), "normal"),
        "attn.mu": ((H, D), "normal"),
        "post_norm.g": ((h,), "normal"),
        "mlp.w_gate": ((h, f), "normal"),
        "mlp.w_up": ((h, f), "normal"),
        "mlp.w_down": ((f, h), "normal"),
    }
    for i in range(d["layers"]):
        for k in LAYER_KEYS:
            out[f"layers.{i}.{k}"] = shapes[k]
    return out


def gain(leaf):
    """A norm's ``g`` from its seeded leaf: four times it (0.08 N(0, 1))."""
    return 4.0 * leaf.astype(F32)


def pooling_vector(leaf):
    """``phi`` or ``mu [H, D]`` from its seeded leaf: ``D^-1/2 clip(N(0, 1),
    -1, 1)``, rounded to bfloat16 (the program holds these very numbers)."""
    z = jnp.clip(leaf.astype(F32) / SEED_STD, -1.0, 1.0)
    return jax.lax.reduce_precision(z * leaf.shape[-1] ** -0.5,
                                    exponent_bits=8, mantissa_bits=7)


def _rms(x, leaf, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + gain(leaf))


def _rope(x, pos, theta):
    """Rotate-half rotary of ``x [S, H, D]`` at positions ``pos [S]``."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos.astype(F32)[:, None] * inv[None, :]             # [S, D/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def summaries(k, v, phi, mu, chunk: int):
    """``(k~, v~) [T / chunk, H, D]`` of ``k``/``v [T, H, D]``."""
    T, H, D = k.shape
    kc, vc = k.reshape(T // chunk, chunk, H, D), v.reshape(T // chunk, chunk,
                                                           H, D)
    a = jax.nn.softmax(jnp.sum(kc * phi, axis=-1, keepdims=True) * D ** -0.5,
                       axis=1)
    return jnp.sum(a * kc, axis=1) + mu, jnp.sum(a * vc, axis=1)


def summaries_uniform(k, v, phi, mu, chunk: int):
    """A wrong summary (the tests' broken path): the chunk's plain mean."""
    z = jnp.zeros_like(phi)
    return summaries(k, v, z, z, chunk)


def _attend(q, o, k_win, v_win, ks, vs, n_sum, d: dict):
    """Queries ``q [n, H, D]`` at places ``o ..`` of their window over the
    window's exact keys ``k_win``/``v_win [W, H, D]`` (rows ``<= `` the
    query's place count) and the first ``n_sum`` summary rows of ``ks``/``vs
    [R, H, D]``; ``Q_BLOCKS`` rows at a time."""
    n, D = q.shape[0], d["head_dim"]
    qb = next((b for b in Q_BLOCKS if n % b == 0), n)
    ok_su = (jnp.arange(ks.shape[0]) < n_sum)[None, None, :]

    def one(args):
        qb_, place = args                       # [qb, H, D], [qb]
        ok_ex = (jnp.arange(k_win.shape[0])[None, :] <= place[:, None])[None]
        s = jnp.concatenate([
            jnp.where(ok_su, jnp.einsum("qhd,khd->hqk", qb_, ks,
                                        precision=HI), -jnp.inf),
            jnp.where(ok_ex, jnp.einsum("qhd,khd->hqk", qb_, k_win,
                                        precision=HI), -jnp.inf)],
            axis=-1) / jnp.sqrt(F32(D))
        p = jax.nn.softmax(s, axis=-1)
        r = ks.shape[0]
        return (jnp.einsum("hqk,khd->qhd", p[..., :r], vs, precision=HI)
                + jnp.einsum("hqk,khd->qhd", p[..., r:], v_win, precision=HI)
                ).reshape(qb, -1)

    out = jax.lax.map(one, (q.reshape(n // qb, qb, *q.shape[1:]),
                            (o + jnp.arange(n)).reshape(n // qb, qb)))
    return out.reshape(n, -1)


def empty_state(d: dict):
    """The cache of a sequence with no token yet, one layer's: the current
    window's exact keys and values ``[W, H, D]``, every summary row there
    can be ``[positions / C, H, D]`` (row ``c`` is chunk ``c``; rows of
    windows not passed yet are not read), and the tokens so far."""
    H, D = d["heads"], d["head_dim"]
    win = jnp.zeros((d["window"], H, D), F32)
    rows = jnp.zeros((d["positions"] // d["chunk"], H, D), F32)
    return win, win, rows, rows, jnp.int32(0)


def block(x, state, lw: dict, *, d_items: tuple, control: bool,
          summarise=summaries):
    """One layer over ``x [n, h]``: the next ``n`` tokens of a sequence whose
    layer state so far is ``state`` (:func:`empty_state`).  The ``n`` tokens
    lie inside one window (:func:`pieces`).  Returns ``(x', state')``; a
    window the tokens complete is summarised into ``state'``."""
    d = dict(d_items)
    n = x.shape[0]
    H, D, W, C = d["heads"], d["head_dim"], d["window"], d["chunk"]
    k_win, v_win, ks, vs, seen = state
    pos = seen + jnp.arange(n)
    o = seen % W                                # the first token's place
    a = _rms(x, lw["input_norm.g"], d["eps"])
    q = _rope(mm(a, lw["attn.wq"], control).reshape(n, H, D), pos, d["theta"])
    k = _rope(mm(a, lw["attn.wk"], control).reshape(n, H, D), pos, d["theta"])
    v = mm(a, lw["attn.wv"], control).reshape(n, H, D)
    k_win = jax.lax.dynamic_update_slice_in_dim(k_win, k, o, axis=0)
    v_win = jax.lax.dynamic_update_slice_in_dim(v_win, v, o, axis=0)
    passed = seen // W                          # windows behind this one
    ctx = _attend(q, o, k_win, v_win, ks, vs, passed * (W // C), d)
    x = x + mm(ctx, lw["attn.wo"], control)
    u = _rms(x, lw["post_norm.g"], d["eps"])
    y = mm(jax.nn.silu(mm(u, lw["mlp.w_gate"], control))
           * mm(u, lw["mlp.w_up"], control), lw["mlp.w_down"], control)
    # the window's summaries, kept where these tokens completed it
    ks_w, vs_w = summarise(k_win, v_win, pooling_vector(lw["attn.phi"]),
                           pooling_vector(lw["attn.mu"]), C)
    done = o + n == W
    at = jnp.minimum(passed * (W // C), ks.shape[0] - W // C)
    ks = jnp.where(done, jax.lax.dynamic_update_slice_in_dim(ks, ks_w, at, 0),
                   ks)
    vs = jnp.where(done, jax.lax.dynamic_update_slice_in_dim(vs, vs_w, at, 0),
                   vs)
    return x + y, (k_win, v_win, ks, vs, seen + n)


def pieces(start: int, stop: int, window: int) -> list:
    """``[start, stop)`` cut at the windows' ends: ``(a, b)`` runs of
    positions that lie inside one window each."""
    out, a = [], start
    while a < stop:
        b = min(stop, (a // window + 1) * window)
        out.append((a, b))
        a = b
    return out


def layer_names(i: int, d: dict = None) -> list:
    return [f"layers.{i}.{k}" for k in LAYER_KEYS]


def layer_weights(w: dict, i: int, d: dict) -> dict:
    return {k: w[f"layers.{i}.{k}"] for k in LAYER_KEYS}


@functools.lru_cache(maxsize=None)
def _jit_block(d_items, control, summarise):
    return jax.jit(functools.partial(block, d_items=d_items, control=control,
                                     summarise=summarise))


def shared_openings(seqs) -> list:
    """``[(length, members)]``: the sequences grouped by a common opening of
    ``length`` tokens (whole blocks of the smallest ``Q_BLOCKS``,
    ``SHARE_FROM`` or more, leaving every member a remainder), each sequence
    in one group; a sequence that shares with none stands alone with length
    0."""
    arrs = [np.asarray(s) for s in seqs]
    groups = []
    for i, a in enumerate(arrs):
        for g in groups:
            b = arrs[g["members"][0]]
            m = min(len(a), len(b))
            diff = np.flatnonzero(a[:m] != b[:m])
            common = int(diff[0]) if len(diff) else m
            common = min(common, g["length"] or common, m - 1)
            common -= common % Q_BLOCKS[-1]
            if common >= SHARE_FROM:
                g["members"].append(i)
                g["length"] = common
                break
        else:
            groups.append({"members": [i], "length": 0})
    return [(g["length"] if len(g["members"]) > 1 else 0, g["members"])
            for g in groups]


def hidden_many(provider, seqs, d: dict, *, control: bool = False,
                summarise=summaries):
    """Final-block hidden states ``[S, h]`` of each sequence of ``seqs`` (on
    the host: a long sequence's are half a gigabyte), layer by layer:
    ``provider(names)`` hands over the named weights (any float dtype) when
    their layer is due, so the whole model is never held.  A common opening
    of several sequences goes through each layer once.  One group of
    sequences at a time goes through all the layers (a layer's weights are
    made again for each group), and an opening is held as its windows'
    parts, never whole: beside a program that fills most of the chip, the
    reference holds one opening and one part's arithmetic."""
    d_items = tuple(sorted(d.items()))
    step = _jit_block(d_items, control, summarise)
    W = d["window"]
    emb = provider(EMBED_KEYS)["embed"]

    def parts_of(tokens, start):
        return [emb[tokens[a - start:b - start]].astype(F32)
                for a, b in pieces(start, start + len(tokens), W)]

    def run(parts, state, lw):
        """The parts through the layer, in place of themselves."""
        for j in range(len(parts)):
            parts[j], state = step(parts[j], state, lw)
        return state

    out = [None] * len(seqs)
    for length, members in shared_openings(seqs):
        first = jnp.asarray(seqs[members[0]])
        opening = parts_of(first[:length], 0)
        rests = [parts_of(jnp.asarray(seqs[m])[length:], length)
                 for m in members]
        for i in range(d["layers"]):
            names = layer_names(i)
            got = provider(names)
            lw = {k: got[n] for k, n in zip(LAYER_KEYS, names)}
            state = run(opening, empty_state(d), lw)
            for rest in rests:
                run(rest, state, lw)
            del state, got, lw
        opened = [np.asarray(x) for x in opening]
        del opening
        for m, rest in zip(members, rests):
            out[m] = np.concatenate(opened + [np.asarray(x) for x in rest],
                                    axis=0)
    return out


def hidden(w: dict, tokens, d: dict, *, control: bool = False,
           summarise=summaries):
    """``hidden_many`` of one sequence from a whole tree ``w``."""
    return hidden_many(lambda names: {n: w[n] for n in names}, [tokens], d,
                       control=control, summarise=summarise)[0]


@functools.lru_cache(maxsize=None)
def _jit_head(eps, control, width):
    def head(g, lm_head, x):
        return mm(_rms(x, g, eps), lm_head[:, :width], control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    """The next byte's logits ``[rows, V]``: predictor 0 of the head."""
    return _jit_head(d["eps"], control, d["vocab"])(
        w["norm.g"], w["lm_head"], x_rows)


def logits_all(w: dict, x_rows, d: dict):
    """Every predictor's logits ``[rows, P, V]``."""
    P, V = d["pred_heads"], d["vocab"]
    return _jit_head(d["eps"], False, P * V)(
        w["norm.g"], w["lm_head"], x_rows).reshape(-1, P, V)

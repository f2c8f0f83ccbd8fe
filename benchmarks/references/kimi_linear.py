"""Plain reference of Kimi-Linear-48B-A3B (moonshotai/Kimi-Linear-48B-A3B-
Instruct ``config.json``, ``model_type`` kimi_linear; *Kimi Linear*,
arXiv:2510.26692): a stack whose layers mix the sequence with a gated delta
rule with a per-channel forget gate (KDA), three to one with latent attention
without positions (MLA, NoPE); a leading dense SwiGLU and then sigmoid-routed
experts with a selection bias and one shared expert; an untied head.

``jax.numpy``, float32, ``highest`` matmul precision, no kernels, no cache, no
batching.  It imports nothing of the program and is given seeded weights by
the benchmark.  Serving only.

The layer (``x`` the float32 residual stream; pre-norm):

    h  = x + Op(RMSNorm(x))          Op = KDA or MLA by linear_attn_config
    x' = h + FF(RMSNorm(h))          FF = SwiGLU for l < first_k_dense_replace,
                                          else experts + the shared expert

- **KDA**, ``H`` heads of ``D``: ``q~ = n W_q``, ``k~ = n W_k``, ``v~ = n
  W_v``; each through a depthwise causal convolution of ``taps`` taps (zeros
  before position 0, no bias) and SiLU; per head ``q = l2norm(q') * D^-1/2``,
  ``k = l2norm(k')``, ``v = v'`` (``l2norm(a) = a / sqrt(sum a^2 + 1e-6)``).
  ``g = -exp(A_log[h]) * softplus((n W_fa) W_fb + dt_bias)`` a channel;
  ``beta = sigmoid(n W_beta)`` a head.  **Token by token**, ``S [D, D]`` a
  head from zeros: ``S' = Diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S^T q_t`` — a ``lax.scan`` over the positions, not
  the chunked form the program runs.  ``y = [RMSNorm_head(o) *
  sigmoid((n W_ga) W_gb)] W_o``.
- **MLA**: ``q = n W_q -> [H, nope + rope]``; ``[c | k_r] = n W_kva``, ``c_kv
  = RMSNorm(c)``; **no rotation**; per head ``k = [c_kv W^K_h | k_r]``, ``v =
  c_kv W^V_h`` (``W_kvb``); scores ``q . k / sqrt(nope + rope)`` over ``s <=
  t``, one softmax; then ``W_o``.  Written out per head: no absorption.
- **Experts**: ``s = sigmoid(m W_r)`` in float32; the top ``k`` of ``s + b``;
  weights ``s[chosen] / sum * routed_scaling_factor``; ``y = sum w_e
  SwiGLU_e(m)`` over the held experts, ``+ SwiGLU_shared(m)``.
- final RMSNorm, logits ``x W_head``.

Departures from the published model, each also a line of the configuration's
``assumed``: *held experts* (only the experts ``held = [start, stop)`` add
their terms; the router scores all and normalises over all the chosen), no
multi-token prediction module, and *seeded weights*: normal(0, 0.02), gains 1
+ 0.1 N(0, 1), the selection bias seeded like a gain (a common offset
changes no choice, so this is 0.1 N(0, 1): dropping it shows), the
convolution's taps like a gain (a tap has a fan-in of 4); ``A_log`` and
``dt_bias`` from gain-like leaves by :func:`gate_parameters`, so that the
decay ``exp(g)`` spans about (0.4, 0.97) a token over heads and channels and
is not all ones.  The router is float32 under ``control`` too, and so are the
gates' elementwise part and the recurrence: a lower-precision deployment
keeps them so; ``control`` rounds every matmul's operands.

**How a long sequence fits.**  MLA takes a head at a time and its queries
``ROW_BLOCK`` rows at a time, KDA and the dense layer their rows ``ROW_BLOCK``
at a time; the recurrence holds one ``[H, D, D]`` state.  Sequences that open with the same tokens share them
(``hidden_many``): the common opening goes through each layer once and each
sequence's remainder is computed behind what the opening left — its
latents, or ``S`` and the last columns of ``[q~ | k~ | v~]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references._common import F32, mm

NORM_KEYS = ("input_norm.g", "post_norm.g")
KDA_KEYS = ("kda.wq", "kda.wk", "kda.wv", "kda.conv", "kda.w_fa", "kda.w_fb",
            "kda.a", "kda.dt", "kda.w_beta", "kda.w_ga", "kda.w_gb",
            "kda.o_norm.g", "kda.wo")
MLA_KEYS = ("attn.wq", "attn.wkv_a", "attn.kv_norm.g", "attn.wkv_b",
            "attn.wo")
DENSE_KEYS = ("mlp.w_gate", "mlp.w_up", "mlp.w_down")
MOE_KEYS = ("moe.router", "moe.bias", "moe.w_gate", "moe.w_up", "moe.w_down",
            "moe.shared.w_gate", "moe.shared.w_up", "moe.shared.w_down")
#: an expert layer's keys under KDA (``layer_keys`` has each layer's own)
LAYER_KEYS = NORM_KEYS + KDA_KEYS + MOE_KEYS
EMBED_KEYS = ("embed",)
HEAD_KEYS = ("norm.g", "lm_head")
Q_BLOCK = 64             # a shared opening is whole multiples of this
ROW_BLOCK = 512          # rows of an operator or of the dense layer at once
SHARE_FROM = 1024        # a common opening shorter than this is not shared
HI = jax.lax.Precision.HIGHEST
KDA, MLA = "kda", "mla"


def dims(config: dict) -> dict:
    held = tuple(int(x) for x in config["held_experts"])
    if held[1] - held[0] != int(config["num_experts"]):
        raise ValueError("num_experts is the count of held_experts")
    la = config["linear_attn_config"]
    n = int(config["num_hidden_layers"])
    full = sorted(int(i) - 1 for i in la["full_attn_layers"])
    kda = sorted(int(i) - 1 for i in la["kda_layers"])
    if sorted(full + kda) != list(range(n)):
        raise ValueError("linear_attn_config names every layer 1..n once")
    if config.get("q_lora_rank") is not None or not config["mla_use_nope"] \
            or config["moe_router_activation_func"] != "sigmoid" \
            or not config["moe_renormalize"] \
            or int(config["num_expert_group"]) != 1 \
            or int(config["topk_group"]) != 1 \
            or config["tie_word_embeddings"]:
        raise ValueError("one query projection, no rotation, sigmoid scores "
                         "renormalised over a plain top-k and an untied head "
                         "are what is built")
    kinds = tuple(MLA if i in full else KDA for i in range(n))
    return {"hidden": int(config["hidden_size"]), "layers": n,
            "dense_layers": int(config["first_k_dense_replace"]),
            "kinds": kinds,
            "attn_layers": len(full), "state_layers": len(kda),
            "heads": int(config["num_attention_heads"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "kda_heads": int(la["num_heads"]), "kda_dim": int(la["head_dim"]),
            "taps": int(la["short_conv_kernel_size"]),
            "ffn": int(config["intermediate_size"]),
            "moe_ffn": int(config["moe_intermediate_size"]),
            "experts": int(config["router_experts"]),
            "held": held,
            "top_k": int(config["num_experts_per_token"]),
            "shared": int(config["num_shared_experts"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "vocab": int(config["vocab_size"]),
            "positions": int(config["model_max_length"]),
            "eps": float(config["rms_norm_eps"])}


def layer_keys(i: int, d: dict) -> tuple:
    """The leaves of layer ``i``: its kind of operator, its kind of
    feed-forward."""
    return NORM_KEYS + (KDA_KEYS if d["kinds"][i] == KDA else MLA_KEYS) \
        + (DENSE_KEYS if i < d["dense_layers"] else MOE_KEYS)


def weight_shapes(config: dict) -> dict:
    d = dims(config)
    h, H = d["hidden"], d["heads"]
    Hk, D = d["kda_heads"], d["kda_dim"]
    f, mf, rank = d["ffn"], d["moe_ffn"], d["kv_rank"]
    G = d["held"][1] - d["held"][0]
    out = {"embed": ((d["vocab"], h), "normal"), "norm.g": ((h,), "scale"),
           "lm_head": ((h, d["vocab"]), "normal")}
    shapes = {
        "input_norm.g": ((h,), "scale"), "post_norm.g": ((h,), "scale"),
        "kda.wq": ((h, Hk * D), "normal"), "kda.wk": ((h, Hk * D), "normal"),
        "kda.wv": ((h, Hk * D), "normal"),
        # a tap has a fan-in of 4, not of ``hidden``
        "kda.conv": ((d["taps"], 3 * Hk * D), "scale"),
        "kda.w_fa": ((h, D), "normal"), "kda.w_fb": ((D, Hk * D), "normal"),
        # gain-like leaves that ``gate_parameters`` maps to A_log, dt_bias
        "kda.a": ((Hk,), "scale"), "kda.dt": ((Hk * D,), "scale"),
        "kda.w_beta": ((h, Hk), "normal"),
        "kda.w_ga": ((h, D), "normal"), "kda.w_gb": ((D, Hk * D), "normal"),
        "kda.o_norm.g": ((D,), "scale"), "kda.wo": ((Hk * D, h), "normal"),
        "attn.wq": ((h, H * (d["nope"] + d["rope"])), "normal"),
        "attn.wkv_a": ((h, rank + d["rope"]), "normal"),
        "attn.kv_norm.g": ((rank,), "scale"),
        "attn.wkv_b": ((rank, H * (d["nope"] + d["v"])), "normal"),
        "attn.wo": ((H * d["v"], h), "normal"),
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


def _bf16_exact(a):
    return jax.lax.reduce_precision(a.astype(F32), exponent_bits=8,
                                    mantissa_bits=7)


def gate_parameters(a, dt):
    """``(A_log [H], dt_bias [H D])`` from the gain-like seeded leaves (1 +
    0.1 N(0, 1)): ``A_log = 8 (a - 1) - 1.2`` (``exp`` of it about 0.13 to
    0.67 over the heads) and ``dt_bias = 5 (dt - 1)`` (the softplus about 0.4
    to 1.1 over the channels), each a value bfloat16 holds exactly like every
    other seeded number."""
    return _bf16_exact(8.0 * (a.astype(F32) - 1.0) - 1.2), \
        _bf16_exact(5.0 * (dt.astype(F32) - 1.0))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g.astype(F32)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def short_conv(z, before, filt):
    """``c[t] = sum_j filt[j] * z[t - (L - 1) + j]`` over ``z [S, w]`` with
    the ``L - 1`` columns ``before`` in front (zeros: the sequence's start)."""
    S, taps = z.shape[0], filt.shape[0]
    ext = jnp.concatenate([before, z], axis=0)
    return sum(filt[j].astype(F32)[None, :] * ext[j:j + S]
               for j in range(taps))


def delta_rule(q, k, v, g, beta, s0):
    """The recurrence, token by token: ``q``/``k``/``v``/``g [S, H, D]``,
    ``beta [S, H]``, ``s0 [H, D, D]``; returns ``(o [S, H, D], S after the
    last token)``."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, :, None]
        pred = jnp.einsum("hkv,hk->hv", s, kt, precision=HI)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - pred))[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def kda_operands(a, before, lw: dict, d: dict, control: bool):
    """``(q, k, v, g, beta, z)`` of the normed rows ``a [n, h]`` behind the
    ``taps - 1`` columns ``before`` of ``[q~ | k~ | v~]``."""
    n = a.shape[0]
    H, D = d["kda_heads"], d["kda_dim"]
    z = jnp.concatenate([mm(a, lw[k], control)
                         for k in ("kda.wq", "kda.wk", "kda.wv")], axis=1)
    qkv = jax.nn.silu(short_conv(z, before, lw["kda.conv"]))
    q, k, v = (qkv[:, i * H * D:(i + 1) * H * D].reshape(n, H, D)
               for i in range(3))
    a_log, dt_bias = gate_parameters(lw["kda.a"], lw["kda.dt"])
    f = mm(mm(a, lw["kda.w_fa"], control), lw["kda.w_fb"], control)
    g = -jnp.exp(a_log)[None, :, None] \
        * jax.nn.softplus(f + dt_bias[None, :]).reshape(n, H, D)
    beta = jax.nn.sigmoid(mm(a, lw["kda.w_beta"], control))
    return _l2norm(q) * D ** -0.5, _l2norm(k), v, g, beta, z


def _kda_rows(a, past, lw: dict, d: dict, control: bool):
    n = a.shape[0]
    H, D = d["kda_heads"], d["kda_dim"]
    q, k, v, g, beta, z = kda_operands(a, past[1], lw, d, control)
    o, s = delta_rule(q, k, v, g, beta, past[0])
    gate = jax.nn.sigmoid(mm(mm(a, lw["kda.w_ga"], control), lw["kda.w_gb"],
                             control)).reshape(n, H, D)
    o = _rms(o, lw["kda.o_norm.g"], d["eps"]) * gate
    cols = jnp.concatenate([past[1], z], axis=0)[-(d["taps"] - 1):]
    return mm(o.reshape(n, H * D), lw["kda.wo"], control), (s, cols)


def kda(a, past, lw: dict, d: dict, control: bool):
    """The KDA operator over ``a [n, h]`` behind ``past = (S [H, D, D],
    columns [taps - 1, 3 H D])``; returns ``(y [n, h], past of the
    successors)``.  ``ROW_BLOCK`` rows at a time where that divides ``n``,
    each block behind what the one before left (the same numbers: the
    operator is causal and its memory is ``past``)."""
    n = a.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return _kda_rows(a, past, lw, d, control)
    past, y = jax.lax.scan(
        lambda p, rows: _kda_rows(rows, p, lw, d, control)[::-1], past,
        a.reshape(n // ROW_BLOCK, ROW_BLOCK, -1))
    return y.reshape(n, -1), past


def _attend_block(qb, qpos, k, v):
    """One head's block of queries ``qb [n, Dqk]`` at positions ``qpos [n]``
    over keys ``k [T, Dqk]``, values ``v [T, Dv]`` at positions ``0 ..``."""
    keep = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    s = jnp.einsum("qd,kd->qk", qb, k, precision=HI) \
        / jnp.sqrt(F32(qb.shape[-1]))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("qk,kd->qd", p, v, precision=HI)


def mla(a, past, lw: dict, d: dict, control: bool):
    """Latent attention over ``a [n, h]`` behind ``past = (c_kv [T, rank],
    k_r [T, rope])`` of the tokens before them; nothing rotated.  A head at a
    time, its keys ``[c_kv W^K_h | k_r]`` and values ``c_kv W^V_h`` written
    out (no absorption), its queries ``ROW_BLOCK`` rows at a time."""
    n = a.shape[0]
    H, nope, rope, dv, rank = (d["heads"], d["nope"], d["rope"], d["v"],
                               d["kv_rank"])
    q = mm(a, lw["attn.wq"], control).reshape(n, H, nope + rope)
    ckr = mm(a, lw["attn.wkv_a"], control)
    offset = past[0].shape[0]
    c_kv = jnp.concatenate([past[0], _rms(
        ckr[:, :rank], lw["attn.kv_norm.g"], d["eps"])], axis=0)
    k_r = jnp.concatenate([past[1], ckr[:, rank:]], axis=0)
    qb = ROW_BLOCK if n % ROW_BLOCK == 0 else n
    pos = (offset + jnp.arange(n)).reshape(n // qb, qb)

    def head(x):
        q_h, w_h = x                              # [n, nope + rope], [rank, nope + v]
        kv = mm(c_kv, w_h, control)
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)
        return jax.lax.map(
            lambda y: _attend_block(y[0], y[1], k, kv[:, nope:]),
            (q_h.reshape(n // qb, qb, -1), pos)).reshape(n, dv)

    w = lw["attn.wkv_b"].astype(F32).reshape(rank, H, nope + dv)
    ctx = jax.lax.map(head, (q.transpose(1, 0, 2), w.transpose(1, 0, 2)))
    return mm(ctx.transpose(1, 0, 2).reshape(n, H * dv), lw["attn.wo"],
              control), (c_kv, k_r)


def _swiglu(m, w_gate, w_up, w_down, control):
    return mm(jax.nn.silu(mm(m, w_gate, control)) * mm(m, w_up, control),
              w_down, control)


def _by_rows(f, m):
    n = m.shape[0]
    if n <= ROW_BLOCK or n % ROW_BLOCK:
        return f(m)
    return jax.lax.map(f, m.reshape(n // ROW_BLOCK, ROW_BLOCK, -1)
                       ).reshape(n, -1)


def route(m, router, bias, d: dict):
    """``(chosen [S, k], weights [S, k])`` over ALL the router's experts."""
    s = jax.nn.sigmoid(mm(m, router, False))
    _, chosen = jax.lax.top_k(s + bias.astype(F32)[None, :], d["top_k"])
    w = jnp.take_along_axis(s, chosen, axis=1)
    return chosen, w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) \
        * d["route_scale"]


def experts(m, lw: dict, d: dict, control: bool, held=None):
    """The routed part the experts ``held`` give (default: the chip's own),
    without the shared expert; ``lw``'s stacks start at ``d["held"][0]``."""
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


def shared_expert(m, lw: dict, control: bool):
    return _swiglu(m, lw["moe.shared.w_gate"], lw["moe.shared.w_up"],
                   lw["moe.shared.w_down"], control)


def block(x, past, lw: dict, *, d_items: tuple, control: bool, index: int):
    """Layer ``index`` over ``x [n, h]``, the tokens behind ``past`` (of an
    MLA layer the latents of the tokens before them, of a KDA layer ``S``
    and the last columns).  Returns ``(x', past of the successors)``."""
    d = dict(d_items)
    a = _rms(x, lw["input_norm.g"], d["eps"])
    op = kda if d["kinds"][index] == KDA else mla
    y, past = op(a, past, lw, d, control)
    x = x + y
    mid = _rms(x, lw["post_norm.g"], d["eps"])
    if index < d["dense_layers"]:
        ff = _by_rows(lambda m: _swiglu(m, lw["mlp.w_gate"], lw["mlp.w_up"],
                                        lw["mlp.w_down"], control), mid)
    else:
        ff = experts(mid, lw, d, control) + shared_expert(mid, lw, control)
    return x + ff, past


def layer_names(i: int, d: dict = None) -> list:
    keys = LAYER_KEYS if d is None else layer_keys(i, d)
    return [f"layers.{i}.{k}" for k in keys]


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
    if d["kinds"][i] == KDA:
        H, D = d["kda_heads"], d["kda_dim"]
        return (jnp.zeros((H, D, D), F32),
                jnp.zeros((d["taps"] - 1, 3 * H * D), F32))
    return (jnp.zeros((0, d["kv_rank"]), F32), jnp.zeros((0, d["rope"]), F32))


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
    def head(g, lm_head, x):
        return mm(_rms(x, g, eps), lm_head, control)
    return jax.jit(head)


def logits_rows(w: dict, x_rows, d: dict, *, control: bool = False):
    return _jit_head(d["eps"], control)(w["norm.g"], w["lm_head"], x_rows)

"""The DeepSeek-V3-shaped decoder (latent attention over a one-vector-per-token
paged pool, sigmoid-routed held experts with a shared one) at a tiny preset
(hidden 64, 4 heads, ranks 48/32, nope 16 / rope 8 / v 16, 16 experts top-4 of
which 4 held, 1 + 2 layers, vocabulary 512), against the benchmark's plain
reference (``benchmarks/references/deepseek_v3.py``: float32, non-absorbed, a
loop over the held experts; it imports nothing of the program)."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.serving.kv_cache import CacheSpec
from paddle_tpu.serving.paging import PagedKVCache

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, compiled_steps, f32, family, tokens, want,
    test_full_forward_equals_the_reference,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype)

from benchmarks.harness import weights                        # noqa: E402

FAMILY = FAMILIES["deepseek_v3"]
REF, dm, SEED = FAMILY.ref, FAMILY.models, FAMILY.seed
seeded, reference_logits = FAMILY.seeded, FAMILY.reference_logits


# -- (a) float32 against the reference ---------------------------------------

@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefix_tail_prefill_and_decode_through_the_latent_pool(
        f32, tokens, kernel):
    """Slot 0 prefills a 16-token prefix; slot 2 shares its two blocks and
    prefills the 21-token tail behind them (bucket 32); then 8 tokens are
    decoded (teacher-forced) with slot 1 idle on the scratch block.  Every
    logit row the engine would sample from equals the reference's full
    forward."""
    model, tree, d = f32
    want = reference_logits(tree, d, tokens)
    cache = PagedKVCache(num_slots=3, num_layers=d["layers"], max_seq=64,
                         sides=model.cache_spec().sides, block_size=BLOCK,
                         kernel=kernel)
    assert [tuple(b.shape) for b in cache.buffers()] == \
        [(25, BLOCK, 1, 128)] * d["layers"]        # one buffer a layer

    prefill, decode = compiled_steps(model, cache, counts="expert_counts")

    assert cache.begin_sequence(0, [], 0, 16)
    np.testing.assert_allclose(prefill(0, tokens[:16], 0, 16), want[15],
                               atol=1e-4, rtol=0)
    shared = list(cache.owned_blocks(0))
    assert cache.begin_sequence(2, shared, 16, 32)
    tail = np.zeros(32, np.int32)
    tail[:21] = tokens[16:37]
    np.testing.assert_allclose(prefill(2, tail, 16, 37), want[36],
                               atol=1e-4, rtol=0)
    active = np.asarray([0, 0, 1], np.int32)
    for pos in range(37, 43):
        assert cache.ensure_capacity(2, pos)
        step = np.zeros((3, 1), np.int32)
        step[2, 0] = tokens[pos]
        out, counts = decode(step, active)
        np.testing.assert_allclose(out[2, 0], want[pos], atol=1e-4, rtol=0)
        held, touched = (sum(col) for col in zip(*counts))
        assert len(counts) == 2 and 0 <= touched <= held <= 8
    assert cache.check_invariants() == []


# -- (b) bfloat16 through create_engine, and the control that fails ----------

def test_bf16_engine_serves_within_a_tolerance_the_fp8_control_exceeds():
    """Greedy serving in bf16 through ``create_engine``: every served token's
    reference logit lies within ``TOL`` of the reference's best at its
    position.  bf16 keeps 8 bits (a rounding is 2**-9 = 0.002 relative), the
    logits here are at most 0.7, so a sound run's logits are off by about
    1e-3 and a served token can trail the reference's first choice by that
    much where the two nearly tie (read here: 4e-4).  The fp8 control (3
    mantissa bits, 2**-4 a rounding) puts first what the reference ranks
    9e-3 below its best.  0.003 lies between, several times from either."""
    TOL = 0.003
    model, tree, d = seeded("bfloat16")
    eng = inference.create_engine(model, block_size=BLOCK,
                                  min_bucket=16, max_seq=64, num_slots=4)
    eng.warmup()
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, (n,), dtype=np.int32)
               for n in (19, 33, 40)]
    prompts.append(np.concatenate([prompts[1][:16], prompts[0][:9]]))
    reqs = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    eng.run()
    st = eng.stats()
    assert not any(st["failures"].values())
    assert st["paging"]["prefix"]["hit_tokens"] >= 16
    moe = st["moe"]
    assert moe["tokens"] > 0 and moe["layer_steps"] % 2 == 0
    assert 0 < moe["experts_touched"] <= moe["assignments_held"] \
        <= moe["tokens"] * 4 * 2
    worst = control = 0.0
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.output_ids, np.int32)
        assert len(out) == 10
        full = np.concatenate([p, out])
        rows = slice(len(p) - 1, len(full) - 1)
        want = reference_logits(tree, d, full)[rows]
        best = want.max(axis=-1)
        worst = max(worst, float((best - want[np.arange(10), out]).max()))
        low = np.asarray(REF.logits_rows(
            {k: tree[k] for k in REF.HEAD_KEYS},
            REF.hidden(tree, jnp.asarray(full), d, control=True), d,
            control=True))[rows]
        control = max(control, float(
            (best - want[np.arange(10), low.argmax(-1)]).max()))
    assert worst <= TOL < control, (worst, control)


def test_cold_warm_and_long_tail_admissions_serve_the_cache_free_tokens():
    """Three admissions through the engine's Pallas path in float32: a cold
    prompt (every pair up-projected: the causal square), a short question
    behind the 48 tokens it left cached, and a long tail behind 16 of them
    (both: the square up-projected, the rectangle under the prefix absorbed,
    merged).  Each serves the greedy tokens of the model's cache-free
    forward (one masked absorbed softmax), and its ``engine.prefill`` span
    carries the pairs of its real tokens by form."""
    import time

    from paddle_tpu.obs import spans as _spans

    model, _tree, _d = seeded("float32", max_position_embeddings=256)
    eng = inference.create_engine(model, block_size=BLOCK, min_bucket=16,
                                  max_seq=128, num_slots=4)
    assert eng.kernel == "pallas"
    eng.warmup(buckets=[16, 64])
    rng = np.random.default_rng(9)
    doc = rng.integers(0, 512, (48,), dtype=np.int32)
    prompts = [np.concatenate([doc, rng.integers(0, 512, (n,), np.int32)])
               for n in (5, 7)]
    prompts.append(np.concatenate([doc[:16],
                                   rng.integers(0, 512, (60,), np.int32)]))
    t0 = time.perf_counter()
    reqs = []
    for p in prompts:                 # one at a time: the next one's hit is
        reqs.append(eng.add_request(p, max_new_tokens=6))   # what this left
        eng.run()
    st = eng.stats()
    assert not any(st["failures"].values())
    for p, r in zip(prompts, reqs):
        out = np.asarray(r.output_ids, np.int32)
        assert len(out) == 6
        full = np.concatenate([p, out])
        lg = np.asarray(model(paddle.to_tensor(full[None]))._value())[0]
        rows = lg[len(p) - 1:len(full) - 1]
        assert (rows.max(-1) - rows[np.arange(6), out]).max() <= 1e-4
    fills = [(r[4]["bucket"], r[4]["latent_pairs_upprojected"],
              r[4]["latent_pairs_absorbed"])
             for r in _spans.snapshot(t0) if r[0] == "engine.prefill"]
    assert fills == [(64, 53 * 54 // 2, 0), (16, 7 * 8 // 2, 7 * 48),
                     (64, 60 * 61 // 2, 60 * 16)]
    assert st["latent"] == {
        "prefills": 3, "pairs_upprojected": sum(f[1] for f in fills),
        "pairs_absorbed": sum(f[2] for f in fills)}
    assert eng.health()["kv_block_invariants"] == "ok"


# -- (c) the share test -------------------------------------------------------

def test_the_four_shares_and_the_shared_expert_once_are_the_whole_layer():
    """Each of four chips holds 4 of the 16 experts.  What the four compute
    for their own experts, plus the shared expert counted once, is what the
    uncut reference gives for the whole layer."""
    cfg = FAMILY.tiny_config(n_routed_experts=16, held_experts=[0, 16])
    d = REF.dims(cfg)
    names = REF.layer_names(1, d)
    got = weights.make(REF.weight_shapes(cfg), SEED, jnp.float32, only=names)
    lw = {k: got[n] for k, n in zip(REF.MOE_KEYS, names)}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(23, 64)),
                    jnp.float32)
    whole = REF.experts(x, lw, d, False) + REF._swiglu(
        x, lw["moe.shared.w_gate"], lw["moe.shared.w_up"],
        lw["moe.shared.w_down"], False)
    chosen, w = dm.route(x, lw["moe.router"], lw["moe.bias"], top_k=4,
                         scale=2.5)
    parts, held_total = [], 0
    for lo in range(0, 16, 4):
        y, n_held, _ = dm.held_experts_forward(
            x, chosen, w, jnp.ones((23,), bool),
            jnp.concatenate([lw["moe.w_gate"], lw["moe.w_up"]],
                            axis=2)[lo:lo + 4],
            lw["moe.w_down"][lo:lo + 4], held=(lo, lo + 4), interpret=True)
        parts.append(y)
        held_total += int(n_held)
    assert held_total == 23 * 4            # every assignment on one chip
    shared = dm._swiglu(
        x, jnp.concatenate([lw["moe.shared.w_gate"],
                            lw["moe.shared.w_up"]], axis=1),
        lw["moe.shared.w_down"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=0)
    # and one share alone is what the reference is given for that chip
    np.testing.assert_allclose(
        np.asarray(parts[2]),
        np.asarray(REF.experts(x, lw, d, False, held=(8, 12))),
        atol=1e-5, rtol=0)


# -- (f) dropless -------------------------------------------------------------

def test_a_batch_routed_entirely_to_the_same_experts_loses_nothing():
    """A selection bias that sends all 40 tokens to experts 0-3 (a capacity
    of T * k / E = 10 would drop three in four): every assignment is
    computed, and pad rows and idle slots are not routed at all."""
    rng = np.random.default_rng(11)
    T, h, f, G = 40, 64, 32, 4
    x = jnp.asarray(rng.normal(size=(T, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, 16)) * 0.02, jnp.float32)
    bias = jnp.zeros((16,), jnp.float32).at[:4].set(10.0)
    w_gu = jnp.asarray(rng.normal(size=(G, h, 2 * f)) * 0.1, jnp.float32)
    w_d = jnp.asarray(rng.normal(size=(G, f, h)) * 0.1, jnp.float32)
    chosen, w = dm.route(x, router, bias, top_k=4, scale=2.5)
    assert sorted(np.unique(np.asarray(chosen))) == [0, 1, 2, 3]
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-5)
    live = jnp.arange(T) < 33
    y, n_held, n_touched = dm.held_experts_forward(
        x, chosen, w, live, w_gu, w_d, held=(0, 4), interpret=True)
    assert (int(n_held), int(n_touched)) == (33 * 4, 4)
    want = jnp.zeros_like(x)
    for e in range(G):
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        want = want + w_e[:, None] * dm._swiglu(x, w_gu[e], w_d[e])
    np.testing.assert_allclose(np.asarray(y[:33]), np.asarray(want[:33]),
                               atol=1e-5, rtol=0)
    assert not np.asarray(y[33:]).any()


@pytest.mark.parametrize("kv", ["gpt", "llama"])
def test_kv_models_state_their_cache_and_get_the_buffers_they_had(kv):
    from paddle_tpu.models import (GPTForCausalLM, LlamaForCausalLM,
                                   gpt_tiny, llama_tiny)
    from paddle_tpu.serving import Engine

    model = GPTForCausalLM(gpt_tiny()) if kv == "gpt" \
        else LlamaForCausalLM(llama_tiny())
    cfg = model.config
    kv = getattr(cfg, "n_kv_heads", None) or cfg.num_attention_heads
    assert model.cache_spec() == CacheSpec.kv(cfg.num_hidden_layers, kv,
                                              cfg.head_dim)
    eng = Engine(model, num_slots=2, max_seq=32, min_bucket=8,
                 block_size=8)
    shape = (2 * 4 + 1, 8, kv, 128)
    assert [tuple(b.shape) for b in eng.cache.k] == \
        [shape] * cfg.num_hidden_layers
    assert [tuple(b.shape) for b in eng.cache.v] == \
        [shape] * cfg.num_hidden_layers
    assert eng.cache.nbytes() == 2 * cfg.num_hidden_layers * \
        eng.cache.layer_nbytes()
    assert "moe" not in eng.stats()

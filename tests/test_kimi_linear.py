"""The Kimi-Linear-shaped decoder on the paged engine (ISSUE 44): gated
delta-rule linear attention (KDA) three to one with latent attention without
positions, a cache stated by layer as a latent group and a **state group of
two sides** — the convolution's last columns, which the pool shifts, and the
recurrence's float32 state, which the pool stores, snapshots and restores and
the model computes — and the share of an expert-parallel deployment.
Everything is held against ``benchmarks/references/kimi_linear.py`` (plain
jnp, float32, KDA token by token, imports nothing of the program) and the
kernels against the recurrence itself."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.ops.pallas import kda_kernel as kk
from paddle_tpu.serving.group_cache import (
    ZERO_ROW, GroupedKVCache, GroupedPrefixCache, StatePool)
from paddle_tpu.serving.kv_cache import CacheGroup, CacheSpec

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, STRIDE, compiled_steps, f32, family, tokens, want,
    test_full_forward_equals_the_reference,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype)

from benchmarks.harness import weights                        # noqa: E402

FAMILY = FAMILIES["kimi_linear"]
REF, km = FAMILY.ref, FAMILY.models
seeded, reference_logits, engine = \
    FAMILY.seeded, FAMILY.reference_logits, FAMILY.engine


def grouped(model, kernel="reference", num_blocks=(40, 12), slots=3):
    return GroupedKVCache(
        model.cache_spec().groups, num_slots=slots, max_seq=128,
        dtype="float32", block_size=BLOCK, num_blocks=list(num_blocks),
        kernel=kernel, max_tail=64)


# -- (a) the chunked scan against the recurrence, token by token ----------------

def operands(S, H=2, D=16, seed=0, gate="mild", beta="mixed"):
    """``q, k, v, g, beta, s0``: ``q`` and ``k`` unit vectors (``q`` scaled),
    a non-zero first state; ``gate``: ``mild`` (-1..0 a token), ``strong``
    (-20 a token in every channel, over whole chunks) or ``mixed`` (strong
    rows among mild ones); ``beta``: near 0, near 1, or across (0, 1)."""
    r = np.random.default_rng(seed)
    q, k, v = (r.normal(size=(S, H, D)).astype(np.float32) for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * D ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = -r.uniform(size=(S, H, D)).astype(np.float32)
    if gate == "strong":
        g = np.full((S, H, D), -20.0, np.float32)
    elif gate == "mixed":
        g = np.where(r.uniform(size=(S, H, 1)) < 0.3, 20.0 * g, g)
    b = {"mixed": r.uniform(size=(S, H)), "zero": np.full((S, H), 1e-4),
         "one": np.full((S, H), 1 - 1e-4)}[beta].astype(np.float32)
    s0 = r.normal(size=(H, D, D)).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, k, v, g, b, s0))


@pytest.mark.parametrize("S,n,gate,beta,ends", [
    (128, 128, "mild", "mixed", (16, 64, 128)),     # whole chunks
    (128, 100, "mild", "mixed", (16, 80, 96)),      # a last partial chunk
    (64, 37, "mild", "mixed", (8, 32, 0)),          # less than one chunk
    (192, 192, "strong", "mixed", (64, 72, 192)),   # g = -20 over whole chunks
    (128, 115, "mixed", "mixed", (48, 112, 200)),   # an end past the tail
    (128, 128, "mild", "zero", (64, 0, 0)),         # nothing is written
    (128, 90, "mixed", "one", (16, 88, 0)),         # every write at full strength
    (40, 40, "mild", "mixed", (24, 40, 0)),         # a bucket no chunk divides
])
def test_the_chunked_scan_equals_the_recurrence(S, n, gate, beta, ends):
    """Outputs of every real row, the state at the real end and at each
    planned end (zeros where none is planned or it lies past the tail), in
    float32, from a non-zero first state; no overflow under strong gates."""
    q, k, v, g, b, s0 = operands(S, seed=S + n, gate=gate, beta=beta)
    o, last, kept = kk.kda_chunk_prefill(
        q, k, v, g, b, s0, jnp.asarray(ends, jnp.int32), n, interpret=True)
    want_o, states = kk.kda_recurrence(q[:n], k[:n], v[:n], g[:n], b[:n], s0)
    assert np.isfinite(np.asarray(o[:n])).all()
    np.testing.assert_allclose(np.asarray(o[:n]), np.asarray(want_o),
                               atol=5e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(states[n - 1]),
                               atol=5e-6, rtol=0)
    for e, got in zip(ends, np.asarray(kept)):
        want_e = np.asarray(states[e - 1]) if 0 < e <= n else 0.0
        np.testing.assert_allclose(got, want_e, atol=5e-6, rtol=0)


def test_pad_rows_reach_no_state_whatever_they_hold():
    q, k, v, g, b, s0 = (np.array(a) for a in operands(64, seed=3))
    for a in (q, k, v, g, b):
        a[21:] = np.nan
    o, last, _kept = kk.kda_chunk_prefill(
        *(jnp.asarray(a) for a in (q, k, v, g, b, s0)),
        jnp.zeros((1,), jnp.int32), 21, interpret=True)
    want_o, states = kk.kda_recurrence(*(jnp.asarray(a[:21]) for a in (
        q, k, v, g, b)), jnp.asarray(s0))
    np.testing.assert_allclose(np.asarray(o[:21]), np.asarray(want_o),
                               atol=5e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(last), np.asarray(states[20]),
                               atol=5e-6, rtol=0)


# -- (b) the decode step ----------------------------------------------------------

@pytest.mark.parametrize("active", [(0, 1, 0, 1, 1, 0), (1, 1, 1, 1, 1, 1),
                                    (0, 0, 0, 0, 0, 0), (0, 0, 0, 0, 0, 1)])
def test_a_decode_step_is_one_step_of_the_recurrence_for_the_running_slots(
        active):
    """The running slots' state and output equal one token of the
    recurrence; an idle slot's state is what it was **bit for bit** and its
    output row zero."""
    q, k, v, g, b, _s0 = operands(6, seed=5, gate="mixed")
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(6, 2, 16, 16)).astype(np.float32))
    o, new = kk.kda_decode_step(state, q, k, v, g, b,
                                jnp.asarray(active, jnp.int32),
                                interpret=True)
    for s, runs in enumerate(active):
        if not runs:
            np.testing.assert_array_equal(np.asarray(new[s]),
                                          np.asarray(state[s]))
            assert not np.asarray(o[s]).any()
            continue
        want_o, want_s = kk.kda_recurrence(
            q[s:s + 1], k[s:s + 1], v[s:s + 1], g[s:s + 1], b[s:s + 1],
            state[s])
        np.testing.assert_allclose(np.asarray(o[s]), np.asarray(want_o[0]),
                                   atol=1e-6, rtol=0)
        np.testing.assert_allclose(np.asarray(new[s]), np.asarray(want_s[0]),
                                   atol=1e-6, rtol=0)


# -- the state pool's two kinds of side --------------------------------------------

def pool(**kw):
    kw = dict(dict(block_size=BLOCK, max_tail=64, num_snapshots=12,
                   stride=STRIDE), **kw)
    return StatePool(3, 2, ((3, 6), (2, 4, 4)), "float32", **kw)


def test_a_state_group_states_a_side_of_each_kind_at_most():
    spec = CacheSpec.by_layer([
        CacheGroup((1,), ((1, 40),)),
        CacheGroup((0,), ((3, 96), (2, 16, 16)), state=True, stride=32,
                   chunk=8)],
        kind="latent")
    assert spec.groups[1].stride == 32 and spec.kind == "latent"
    assert (spec.groups[0].chunk, spec.groups[1].chunk) == (0, 8)
    for sides, msg in ((((3, 96),) * 2, "one buffer"),
                       (((2, 4, 4),) * 2, "one buffer"),
                       (((1, 2, 3, 4),), "one buffer")):
        with pytest.raises(ValueError, match=msg):
            CacheSpec.by_layer([CacheGroup((1,), ((1, 40),)),
                                CacheGroup((0,), sides, state=True)])
    with pytest.raises(ValueError, match="no snapshot stride"):
        CacheSpec.by_layer([CacheGroup((0,), ((1, 40),), stride=16)])
    # a scan's chunk belongs to a recurrent side
    with pytest.raises(ValueError, match="beside a recurrent"):
        CacheSpec.by_layer([CacheGroup((1,), ((1, 40),)),
                            CacheGroup((0,), ((3, 96),), state=True, chunk=8)])
    with pytest.raises(ValueError, match="at most"):
        StatePool(2, 1, ((3, 6), (3, 6)), "float32", block_size=BLOCK,
                  max_tail=64)


def test_the_stride_is_the_groups_and_the_default_rows_come_from_bytes(
        monkeypatch):
    from paddle_tpu.serving import group_cache

    p = pool()
    assert (p.stride, p.max_snaps, p.chunk) == (STRIDE, 64 // STRIDE + 1, 0)
    assert pool(chunk=8).chunk == 8
    assert p.recurrent_nbytes() == 2 * 4 * 4 * 4
    assert p.slot_nbytes() == 2 * (3 * 6 * 4 + 128)
    assert [tuple(b.shape) for b in p.buffers()] == [
        (2, 3, 3, 6), (2, 3, 12, 6), (3, 2, 4, 4), (3, 2, 4, 4),
        (12, 2, 4, 4), (12, 2, 4, 4)]
    assert p.nbytes() == (3 + 12) * p.slot_nbytes()
    # a group that states no stride takes the pool's default
    assert StatePool(3, 2, (2, 5), "float32", block_size=BLOCK,
                     max_tail=64).stride == group_cache.SNAPSHOT_STRIDE
    # the default row count: every slot's prefill, as far as the bytes allow
    assert pool(num_snapshots=None).num_blocks == 3 * (p.max_snaps + 1) + 1
    monkeypatch.setattr(group_cache, "SNAPSHOT_POOL_BYTES",
                        5 * p.slot_nbytes())
    assert pool(num_snapshots=None).num_blocks == 5
    st = p.stats()
    assert (st["stride"], st["slot_bytes"], st["state_bytes"],
            st["snapshot_pool_bytes"]) == (
        STRIDE, p.slot_nbytes(), 3 * p.slot_nbytes(), 12 * p.slot_nbytes())


@pytest.mark.parametrize("start,end,want_ends", [
    (0, 40, [32, 16]),
    (0, 30, [24, 16]),
    (0, 64, [64, 48, 32, 16]),      # ends on a stride: no replay row at 56
    (32, 48, [48]),                 # (the shift-only pool keeps 40 there too)
    (32, 33, []),
])
def test_a_recurrent_pool_leaves_no_replay_snapshot_beside_a_strides(
        start, end, want_ends):
    assert pool().snapshot_ends(start, end) == want_ends
    shift_only = StatePool(3, 2, (2, 5), "float32", block_size=BLOCK,
                           max_tail=64, num_snapshots=12, stride=STRIDE)
    last = (end - 1) // BLOCK * BLOCK
    assert shift_only.snapshot_ends(start, end) == (
        [last] if last > start else []) + [e for e in want_ends if e != last]


def test_the_pool_hands_out_the_planned_state_and_takes_the_states_back():
    """A cold plan starts from zeros; the states the model hands back land in
    the slot and in the planned rows (an unplanned one nowhere); a second
    slot planned behind a row starts from it; a decode step's function gets
    the layer's buffer and its result is the layer's from then on."""
    p = pool()
    first, n = p.begin_sequence(1, ZERO_ROW, 0, 40)
    assert (first, n) == (0, 2) and p.wrote(1).keys() == {16, 32}
    s0, ends = p.recurrent_start(1, jnp.int32(1), 64)
    assert not np.asarray(s0).any()
    assert list(np.asarray(ends)) == [16, 32] + [0] * (p.max_snaps - 2)
    r = np.random.default_rng(2)
    last = r.normal(size=(2, 4, 4)).astype(np.float32)
    kept = r.normal(size=(p.max_snaps, 2, 4, 4)).astype(np.float32)
    p.recurrent_finish(1, jnp.int32(1), 64, jnp.asarray(last),
                       jnp.asarray(kept))
    rec = [np.asarray(b.numpy()) for b in p.recurrent]
    snaps = [np.asarray(b.numpy()) for b in p.recurrent_snapshots]
    np.testing.assert_array_equal(rec[1][1], last)
    assert not rec[0].any() and not rec[1][[0, 2]].any()
    np.testing.assert_array_equal(snaps[1][p.wrote(1)[16]], kept[0])
    np.testing.assert_array_equal(snaps[1][p.wrote(1)[32]], kept[1])
    assert np.count_nonzero(snaps[1].reshape(12, -1).any(axis=1)) == 2
    assert not snaps[0].any()                           # row 0 and layer 0
    p.begin_sequence(2, p.wrote(1)[32], 32, 40)
    s0, _ends = p.recurrent_start(1, jnp.int32(2), 16)
    np.testing.assert_array_equal(np.asarray(s0), kept[1])

    def step(state, active):
        assert state.shape == (3, 2, 4, 4) and active.dtype == jnp.int32
        return jnp.float32(7.0), state + active[:, None, None, None]

    assert float(p.recurrent_step(0, step, jnp.asarray([1, 0, 1]))) == 7.0
    after = np.asarray(p.recurrent[0].numpy())
    assert after[0].min() == 1.0 and not after[1].any()


# -- (c), (d) through the cache: logits against the reference's full forward ------

def test_the_engine_builds_a_latent_pool_and_a_state_group_of_two_sides():
    model = FAMILY.tiny_model(dtype="bfloat16")
    spec = model.cache_spec()
    assert spec.kind == "latent" and spec.num_layers == 4 \
        and spec.tail_limit == 0
    eng = inference.create_engine(model, num_slots=2, max_seq=64,
                                  min_bucket=8, block_size=BLOCK,
                                  num_kv_blocks=12, num_state_snapshots=7)
    (latent,), (state,) = eng.cache.pools, eng.cache.states
    assert [tuple(b.shape) for b in latent.buffers()] == [(12, BLOCK, 1, 128)]
    assert tuple(state.state.shape) == (3, 3, 2, 96)
    assert tuple(state.snapshots.shape) == (3, 3, 7, 96)
    assert [tuple(b.shape) for b in state.recurrent] == [(2, 2, 16, 16)] * 3
    assert [tuple(b.shape) for b in state.recurrent_snapshots] == \
        [(7, 2, 16, 16)] * 3
    # the columns in the cache's dtype, the recurrence's state in float32
    assert {str(b.dtype) for b in (*latent.buffers(), state.state,
                                   state.snapshots)} == {"bfloat16"}
    assert {str(b.dtype) for b in (*state.recurrent,
                                   *state.recurrent_snapshots)} == {"float32"}
    assert state.stride == 16 and isinstance(eng.prefix_cache,
                                             GroupedPrefixCache)
    # the published pattern: three KDA layers, then latent attention; the
    # last layer latent too
    kinds = km.KimiLinearConfig().kinds
    assert kinds[:8] == (km.KDA,) * 3 + (km.MLA,) + (km.KDA,) * 3 + (km.MLA,)
    assert len(kinds) == 27 and kinds.count(km.MLA) == 7 \
        and kinds[24:] == (km.KDA, km.KDA, km.MLA)
    gs = eng.stats()["swa"]["groups"]
    assert gs[1]["recurrent"] == [2, 16, 16] and gs[1]["state"] == [3, 96]
    assert gs[1]["state_bytes"] == 2 * state.slot_nbytes()


@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefill_then_decode_through_the_cache(f32, tokens, want, kernel):
    """A cold 40-token prompt; a 24-token tail in another slot that starts
    from the snapshot the first prefill left at 32 (and from the four blocks
    of latents before it); then teacher-forced decode of both slots to 100
    tokens.  Every logit row equals the reference's one full forward, and
    the slot that never ran keeps its state bit for bit."""
    model, _tree, _d = f32
    cache = grouped(model, kernel)
    (latent,), (state,) = cache.pools, cache.states
    prefill, decode = compiled_steps(model, cache)

    assert cache.begin_sequence(0, None, 0, 40, total=128, end=40)
    assert cache.planned == [(ZERO_ROW, 2)]
    np.testing.assert_allclose(prefill(0, tokens[:40], 0, 40), want[39],
                               atol=2e-5, rtol=0)
    blocks, wrote = cache.owned_blocks(0)
    assert wrote.keys() == {16, 32}
    hit = (list(blocks[:4]), wrote[32])
    assert cache.begin_sequence(2, hit, 32, 24, total=128, end=56)
    assert cache.planned == [(wrote[32], 1)]          # 48 (the stride's, last)
    assert latent.allocator.refcount(blocks[0]) == 2  # shared, not copied
    np.testing.assert_allclose(prefill(2, tokens[32:56], 32, 56), want[55],
                               atol=2e-5, rtol=0)
    pos_of = {0: 40, 2: 56}
    idle = [np.asarray(b.numpy())[1].copy() for b in state.recurrent]
    while pos_of[2] < 100:
        active = np.zeros(3, np.int32)
        step = np.zeros((3, 1), np.int32)
        for s, pos in pos_of.items():
            assert cache.ensure_capacity(s, pos)
            active[s], step[s, 0] = 1, tokens[pos]
        out, _counts = decode(step, active)
        for s, pos in pos_of.items():
            np.testing.assert_allclose(out[s, 0], want[pos], atol=2e-5,
                                       rtol=0)
        pos_of = {s: p + 1 for s, p in pos_of.items()}
    for b, was in zip(state.recurrent, idle):
        np.testing.assert_array_equal(np.asarray(b.numpy())[1], was)
        assert np.asarray(b.numpy())[0].any()
    assert cache.check_invariants() == []
    cache.release_slot(0)
    cache.release_slot(2)
    assert latent.allocator.used_blocks == 0 and state.rows_in_use() == 0


@pytest.fixture(scope="module")
def served(f32):
    return engine(f32[0])


def test_a_prefix_hit_restores_a_snapshot_and_serves_the_cold_prompts_tokens(
        f32, served, tokens):
    """The same 70-token prompt three times: cold; behind a hit that
    restores the snapshot its first prefill left (64 of its tokens: the last
    whole block); and, the snapshots gone, behind a hit **shortened to
    nothing for want of one** though every latent block is still cached.
    All three serve the same tokens, which the reference puts first."""
    model, tree, d = f32
    eng, prompt = served, tokens[:70]
    eng.prefix_cache.clear()
    outs = []
    for _ in range(2):
        h = eng.add_request(prompt, max_new_tokens=12)
        eng.run()
        assert h.finished and not h.error
        outs.append(list(h.output_ids))
    st = eng.stats()["state"]
    assert st["prefills_restored"] >= 1 and st["state_bytes_restored"] > 0
    before = eng.prefix_cache.hits_shortened
    for chain in eng.prefix_cache.state_chains:
        chain.clear()
    h = eng.add_request(prompt, max_new_tokens=12)
    eng.run()
    outs.append(list(h.output_ids))
    assert eng.prefix_cache.hits_shortened == before + 1
    assert eng.prefix_cache.last_given_up == 64
    assert outs[0] == outs[1] == outs[2]
    assert FAMILY.served_gap(tree, d, prompt, outs[0]) < 1e-4
    assert eng.health()["kv_block_invariants"] == "ok"


def test_the_spans_say_what_the_state_group_moved(served, tokens):
    import time

    from paddle_tpu.obs import spans as _spans

    eng = served
    eng.prefix_cache.clear()
    weight = eng.cache.states[0].slot_nbytes()
    t0 = time.perf_counter()
    for _ in range(2):
        h = eng.add_request(tokens[:50], max_new_tokens=3)
        eng.run()
    rows = _spans.snapshot(t0)
    pre = [r for r in rows if r[0] == "engine.prefill"]
    steps = [r for r in rows if r[0] == "engine.step"]
    attrs = [r[4] for r in pre]
    assert len(attrs) == 2
    cold, warm = attrs
    # 50 tokens: the last whole block (48) and the strides 16, 32 -> 48 is a
    # stride's too: three rows; the replay hits 48 and writes none new
    assert (cold["state_row"], cold["state_snapshots_written"],
            cold["state_bytes_restored"],
            cold["state_bytes_snapshotted"]) == (0, 3, 0, 3 * weight)
    assert (cold["kda_tail_tokens"], cold["kda_chunks"]) == (50, 1)
    assert warm["state_row"] > 0 and warm["state_bytes_restored"] == weight
    assert (warm["kda_tail_tokens"], warm["kda_chunks"]) == (2, 1)
    assert (cold["latent_pairs_upprojected"],
            cold["latent_pairs_absorbed"]) == (50 * 51 // 2, 0)
    assert (warm["latent_pairs_upprojected"],
            warm["latent_pairs_absorbed"]) == (3, 2 * 48)
    running = [r[4] for r in steps if r[4].get("state_slots")]
    per_slot = 3 * 2 * eng.cache.states[0].recurrent_nbytes()
    assert running and all(a["state_bytes"] == a["state_slots"] * per_slot
                           for a in running)
    assert h.finished


# -- (e) the share of an expert-parallel deployment --------------------------------

def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """16 experts, 4 shares of 4: each share routes over all 16 (the
    selection bias in the choice only), normalises over the 4 chosen and
    computes its own experts' terms **and the shared expert** (which every
    chip holds); the four routed parts plus the shared expert once equal the
    reference's layer with every expert held."""
    ref = REF
    cfg = FAMILY.tiny_config(num_experts=16, held_experts=[0, 16])
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), FAMILY.seed, jnp.float32)
    lw = ref.layer_weights(tree, 1, d)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    shared = np.asarray(ref.shared_expert(x, lw, False))
    whole = np.asarray(ref.experts(x, lw, d, False)) + shared
    parts = []
    for share in range(4):
        held = (4 * share, 4 * share + 4)
        paddle.seed(0)
        layer = FAMILY.moe(km)(FAMILY.adapter.program_config(
            dict(cfg, held_experts=list(held))))
        layer.gate._set_data(lw["moe.router"])
        layer.e_score_correction_bias._set_data(lw["moe.bias"])
        layer.experts_gate_up._set_data(jnp.concatenate(
            [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)[held[0]:held[1]])
        layer.experts_down._set_data(lw["moe.w_down"][held[0]:held[1]])
        layer.shared_experts.gate_up_proj._set_data(jnp.concatenate(
            [lw["moe.shared.w_gate"], lw["moe.shared.w_up"]], axis=1))
        layer.shared_experts.down_proj._set_data(lw["moe.shared.w_down"])
        routed = np.asarray(layer(x[None])[0]) - shared
        np.testing.assert_allclose(
            routed, np.asarray(ref.experts(x, lw, d, False, held=held)),
            atol=2e-5)
        parts.append(routed)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=5e-5)
    assert all(np.abs(p).max() > 1e-4 for p in parts)    # each share adds
    assert np.abs(shared).max() > 1e-4


# -- the latent attention the family shares with deepseek_v3 -----------------------

def test_the_latent_attention_class_has_one_projection_and_rotates_nothing():
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Attention,
                                               deepseek_v3_tiny)

    paddle.seed(0)
    c = km.kimi_linear_tiny()
    attn = DeepseekV3Attention(c)
    names = {n for n, _p in attn.named_parameters()}
    assert "q_proj" in names and not names & {
        "q_a_proj", "q_a_layernorm", "q_b_proj"}
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 12, 64)),
                    jnp.float32)
    y = np.asarray(attn(x))
    # no positions: the last row's output does not change when the rows
    # before it change places (a rotated key part would tell them apart)
    perm = np.r_[np.random.default_rng(1).permutation(11), 11]
    np.testing.assert_allclose(np.asarray(attn(x[:, perm]))[0, -1],
                               y[0, -1], atol=1e-5)
    paddle.seed(0)
    rotating = DeepseekV3Attention(deepseek_v3_tiny())
    z = np.asarray(rotating(x))
    assert np.abs(np.asarray(rotating(x[:, perm]))[0, -1] - z[0, -1]).max() \
        > 1e-4
    assert {"q_a_proj", "q_a_layernorm", "q_b_proj"} <= {
        n for n, _p in rotating.named_parameters()}

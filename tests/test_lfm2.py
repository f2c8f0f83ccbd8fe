"""The LFM2-shaped decoder on the paged engine (ISSUE 40): gated short
convolutions three to one with grouped-query attention, a cache stated by
layer as an attention group and a **state group** — one buffer of fixed size a
slot a layer that every token rewrites, kept by snapshot so that a prefix hit
can restore it — and the share of an expert-parallel deployment.  Everything is
held against ``benchmarks/references/lfm2_moe.py`` (plain jnp, float32,
imports nothing of the program)."""
import time

import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.obs import spans as _spans
from paddle_tpu.serving.group_cache import (
    ZERO_ROW, GroupedKVCache, GroupedPrefixCache, StatePool)
from paddle_tpu.serving.kv_cache import CacheGroup, CacheSpec

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, STRIDE, compiled_steps, f32, family, tokens, want,
    with_stride,
    test_full_forward_equals_the_reference,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype,
    test_the_shares_layer_outputs_add_up_to_the_uncut_layer)

from benchmarks.harness import weights                        # noqa: E402

FAMILY = FAMILIES["lfm2_moe"]
REF, lm = FAMILY.ref, FAMILY.models
seeded, reference_logits = FAMILY.seeded, FAMILY.reference_logits
engine, greedy_matches = FAMILY.engine, FAMILY.greedy_matches


@pytest.fixture(scope="module")
def small():
    """Two layers, a convolution (dense feed-forward) and an attention layer
    (experts): what the engine's own behaviour is tested on."""
    return seeded(num_hidden_layers=2,
                  layer_types=["conv", "full_attention"])


@pytest.fixture(scope="module")
def cold(small):
    """An engine without a prefix cache: what a cold run serves."""
    return engine(small[0], buckets=(128,), enable_prefix_cache=False)


def grouped(model, kernel="reference", num_blocks=(40, 12), slots=3):
    return with_stride(
        GroupedKVCache, model.cache_spec().groups, num_slots=slots,
        max_seq=128, dtype="float32", block_size=BLOCK,
        num_blocks=list(num_blocks), kernel=kernel, max_tail=64)


# -- (a) the model, its statement, the reference's convolution -----------------

def test_the_references_convolution_is_the_three_term_sum():
    """``short_conv`` against the definition written out a position at a
    time, from a sequence's start (zeros in front) and behind two columns."""
    rng = np.random.default_rng(3)
    z = rng.normal(size=(11, 5)).astype(np.float32)
    filt = rng.normal(size=(3, 5)).astype(np.float32)
    zeros = np.zeros((2, 5), np.float32)
    got = np.asarray(REF.short_conv(jnp.asarray(z), jnp.asarray(zeros),
                                    jnp.asarray(filt)))
    for t in range(11):
        want_t = sum(filt[k] * (z[t - 2 + k] if t - 2 + k >= 0 else 0.0)
                     for k in range(3))
        np.testing.assert_allclose(got[t], want_t, atol=1e-6)
    # the last rows behind the first rows' last two columns: the same numbers
    tail = np.asarray(REF.short_conv(jnp.asarray(z[6:]), jnp.asarray(z[4:6]),
                                     jnp.asarray(filt)))
    np.testing.assert_allclose(tail, got[6:], atol=1e-6)


def test_the_engine_builds_a_pool_a_state_array_and_a_snapshot_pool():
    model = FAMILY.tiny_model(dtype="bfloat16")
    spec = model.cache_spec()
    assert spec.kind == "kv" and spec.num_layers == 4 and spec.tail_limit == 0
    eng = inference.create_engine(model, num_slots=2, max_seq=64,
                                  min_bucket=8, block_size=BLOCK,
                                  num_kv_blocks=12, num_state_snapshots=7)
    (pool,), (state,) = eng.cache.pools, eng.cache.states
    assert [tuple(b.shape) for b in pool.buffers()] == [(12, BLOCK, 2, 128)] * 2
    # [layers, rows, slots | snapshot rows, width]: slots and rows are tiled
    assert tuple(state.state.shape) == (3, 2, 2, 64)
    assert tuple(state.snapshots.shape) == (3, 2, 7, 64)
    assert {str(b.dtype) for b in eng.cache.buffers()} == {"bfloat16"}
    assert isinstance(eng.prefix_cache, GroupedPrefixCache)
    # the published pattern: conv, conv, then attention every fourth
    kinds = lm.Lfm2Config().kinds
    assert kinds[:6] == (lm.CONV, lm.CONV, lm.ATTENTION, lm.CONV, lm.CONV,
                         lm.CONV) and len(kinds) == 40
    assert kinds.count(lm.ATTENTION) == 10 and kinds[38:] == (
        lm.ATTENTION, lm.CONV)


@pytest.mark.parametrize("groups,msg", [
    ([CacheGroup((0,), ((2, 64),), state=True),
      CacheGroup((1,), ((2, 16),) * 2)], "first group counts"),
    ([CacheGroup((0,), ((2, 16),) * 2),
      CacheGroup((1,), ((2, 64),), window=8, state=True)], "no window"),
    ([CacheGroup((0,), ((2, 16),) * 2),
      CacheGroup((1,), ((2, 64),) * 2, state=True)], "one buffer"),
])
def test_a_state_group_is_stated_behind_a_group_that_counts_positions(groups,
                                                                       msg):
    with pytest.raises(ValueError, match=msg):
        CacheSpec.by_layer(groups)


# -- (b) the state pool alone ---------------------------------------------------

def pool(**kw):
    kw = dict(dict(block_size=BLOCK, max_tail=64, num_snapshots=12), **kw)
    return with_stride(StatePool, 3, 2, (2, 5), "float32", **kw)


@pytest.mark.parametrize("start,end,want_ends", [
    (0, 40, [32, 16]),              # the last whole block is a stride's too
    (0, 30, [24, 16]),
    (24, 33, [32]),
    (32, 33, []),                   # one token: no whole block behind it
    (0, 64, [56, 64, 48, 32, 16]),  # the prompt's end is a stride's
    (48, 50, []),
    (8, 17, [16]),
])
def test_snapshot_ends_are_the_strides_and_the_last_whole_block(start, end,
                                                                want_ends):
    assert pool().snapshot_ends(start, end) == want_ends


def test_a_plan_names_the_row_to_start_from_and_the_rows_to_write():
    p = pool()
    first, n = p.begin_sequence(1, ZERO_ROW, 0, 40)
    assert (first, n) == (0, 2) and p.wrote(1).keys() == {16, 32}
    row = np.asarray(p.plan.numpy())[1]
    k = p.max_snaps
    assert k == 64 // STRIDE + 1 and row.shape == (1 + 2 * k,)
    assert row[0] == 0
    assert list(row[1:3]) == [p.wrote(1)[16], p.wrote(1)[32]]
    assert all(row[3:1 + k] == p.num_blocks)          # past the pool: dropped
    assert list(row[1 + k:3 + k]) == [16, 32] and not row[3 + k:].any()
    # a warm-up plans nothing and counts as no admission
    assert p.begin_sequence(2, ZERO_ROW, 0, None) == (0, 0)
    assert (p.cold, p.restored) == (1, 0)
    # a hit's row is held while the slot lives
    hit = p.wrote(1)[32]
    p.allocator.ref(hit)
    p.allocator.mark_cached(hit)                      # as the prefix cache
    p.release_slot(2)
    assert p.begin_sequence(2, hit, 32, 45) == (hit, 1)
    assert p.allocator.refcount(hit) == 3             # slot 1, cache, slot 2
    with pytest.raises(RuntimeError, match="already holds"):
        p.begin_sequence(2, ZERO_ROW, 0, 8)
    p.release_slot(1)
    p.release_slot(2)
    assert p.allocator.refcount(hit) == 1 and p.rows_in_use() == 1
    assert p.check_invariants() == []


def test_a_pool_too_small_skips_snapshots_the_least_wanted_first():
    p = pool(num_snapshots=3)                         # two rows to give
    assert p.begin_sequence(0, ZERO_ROW, 0, 64)[1] == 2
    assert p.wrote(0).keys() == {56, 64}              # the resume's, the end
    assert p.snapshots_skipped == 3 and p.snapshots_written == 2
    # nothing is waited for: a second admission goes without
    assert p.begin_sequence(1, ZERO_ROW, 0, 40) == (0, 0)
    assert p.snapshots_skipped == 5


def _columns(n, width=5, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(1, n, width)).astype(np.float32))


def test_a_prefill_leaves_the_state_of_its_real_end_and_its_snapshots():
    """21 real columns in a bucket of 32 whose pad rows are NaN: the slot's
    state is columns 19 and 20, the snapshot at 16 columns 14 and 15, every
    real position's taps are finite and no NaN reaches either buffer."""
    p = pool()
    z = np.array(_columns(32))
    z[0, 21:] = np.nan
    p.begin_sequence(1, ZERO_ROW, 0, 21)
    taps = p.prefill_update(1, jnp.int32(1), jnp.asarray(z), jnp.int32(0),
                            jnp.int32(21))
    assert len(taps) == 3 and all(t.shape == (1, 32, 5) for t in taps)
    for k in range(3):                                # tap k: z[t - 2 + k]
        got = np.asarray(taps[k])[0, :21]
        want_k = np.concatenate([np.zeros((2 - k, 5)), z[0, :21 - (2 - k)]])
        np.testing.assert_array_equal(got, want_k.astype(np.float32))
    state, snaps = np.asarray(p.state.numpy()), np.asarray(p.snapshots.numpy())
    np.testing.assert_array_equal(state[1, :, 1], z[0, 19:21])
    np.testing.assert_array_equal(snaps[1, :, p.wrote(1)[16]], z[0, 14:16])
    assert np.isfinite(state).all() and np.isfinite(snaps).all()
    assert not state[0].any() and not snaps[:, :, 0].any()   # row 0: zeros
    assert not state[1, :, [0, 2]].any()              # no other slot


def test_a_tail_behind_a_snapshot_sees_the_columns_before_it():
    p = pool()
    z = _columns(40)
    p.begin_sequence(0, ZERO_ROW, 0, 40)
    whole = p.prefill_update(0, jnp.int32(0), z, jnp.int32(0), jnp.int32(40))
    row = p.wrote(0)[32]
    p.begin_sequence(2, row, 32, 40)
    tail = p.prefill_update(0, jnp.int32(2), z[:, 32:], jnp.int32(32),
                            jnp.int32(40))
    for a, b in zip(whole, tail):
        np.testing.assert_array_equal(np.asarray(a)[0, 32:], np.asarray(b)[0])
    state = np.asarray(p.state.numpy())
    np.testing.assert_array_equal(state[0, :, 2], state[0, :, 0])
    # a one-token tail shifts the snapshot's state by its one column
    p.release_slot(2)
    p.begin_sequence(2, row, 32, 33)
    p.prefill_update(0, jnp.int32(2), z[:, 32:40], jnp.int32(32),
                     jnp.int32(33))
    np.testing.assert_array_equal(np.asarray(p.state.numpy())[0, :, 2],
                                  np.asarray(z)[0, 31:33])


def test_a_prompt_in_pieces_is_refused_beside_a_state_group(small):
    """A tail starts from a snapshot or from zeros: a later piece of a prompt
    prefilled in pieces would have to start from what the piece before left
    in the slot, which no statement with a state group asks for."""
    cache = grouped(small[0])
    assert cache.begin_sequence(0, None, 0, 32, end=32)
    with pytest.raises(NotImplementedError, match="prefilled in pieces "
                       "beside a group that keeps state"):
        cache.extend_tail(0, 32, 16)


def test_a_decode_step_shifts_the_running_slots_state_and_no_other():
    p = pool()
    before = np.random.default_rng(1).normal(size=(2, 2, 3, 5)).astype(
        np.float32)
    p.state._set_data(jnp.asarray(before))
    z = _columns(3)[0][:, None, :]                    # [slots, 1, width]
    taps = p.decode_update(1, z, jnp.asarray([1, 0, 1], jnp.int32))
    assert len(taps) == 3 and all(t.shape == (3, 1, 5) for t in taps)
    np.testing.assert_array_equal(np.asarray(taps[0])[:, 0], before[1, 0])
    np.testing.assert_array_equal(np.asarray(taps[1])[:, 0], before[1, 1])
    np.testing.assert_array_equal(np.asarray(taps[2])[:, 0],
                                  np.asarray(z)[:, 0])
    after = np.asarray(p.state.numpy())
    np.testing.assert_array_equal(after[0], before[0])        # another layer
    np.testing.assert_array_equal(after[1, :, 1], before[1, :, 1])   # idle
    for s in (0, 2):
        np.testing.assert_array_equal(after[1, 0, s], before[1, 1, s])
        np.testing.assert_array_equal(after[1, 1, s], np.asarray(z)[s, 0])


# -- (c) through the cache: logits against the reference's full forward ---------

@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefill_then_decode_through_the_cache(f32, tokens, want, kernel):
    """A cold 40-token prompt; a 24-token tail in another slot that starts
    from the snapshot the first prefill left at 32 (and from the four blocks
    before it); then teacher-forced decode of both slots to 100 tokens.
    Every logit row equals the reference's one full forward."""
    model, _tree, _d = f32
    cache = grouped(model, kernel)
    (kv,), (state,) = cache.pools, cache.states
    assert cache.nbytes() == 40 * 2 * BLOCK * 2 * 128 * 4 \
        + 3 * 2 * (3 + 12) * 64 * 4

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
    assert kv.allocator.refcount(blocks[0]) == 2      # shared, not copied
    np.testing.assert_allclose(prefill(2, tokens[32:56], 32, 56), want[55],
                               atol=2e-5, rtol=0)
    pos_of = {0: 40, 2: 56}
    idle = np.asarray(state.state.numpy())[:, :, 1].copy()
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
    # the slot that never ran kept its (zero) state through every step
    np.testing.assert_array_equal(np.asarray(state.state.numpy())[:, :, 1],
                                  idle)
    assert cache.check_invariants() == []
    cache.release_slot(0)
    cache.release_slot(2)
    assert kv.allocator.used_blocks == 0 and state.rows_in_use() == 0


def test_a_tail_from_a_snapshot_is_bit_equal_to_the_cold_path_where_every_layer_is_conv(
        tokens):
    """Four convolution layers and no attention: the logits of an 8-token
    tail that starts from the snapshot at 32 are, bit for bit, those of the
    cold prefill of all 40 tokens (both in the 64 bucket's program)."""
    model, tree, d = seeded(layer_types=["conv"] * 4)
    assert model.cache_spec().groups[0].layers == ()   # counts positions only
    ids = np.zeros(64, np.int32)
    cold = grouped(model)
    assert cold.begin_sequence(0, None, 0, 64, end=40)
    ids[:40] = tokens[:40]
    whole = compiled_steps(model, cold)[0](0, ids, 0, 40)
    np.testing.assert_allclose(
        whole, reference_logits(tree, d, tokens[:40])[39], atol=2e-5, rtol=0)
    warm = grouped(model)
    assert warm.begin_sequence(0, None, 0, 64, end=32)
    ids[:] = 0
    ids[:32] = tokens[:32]
    prefill, _decode = compiled_steps(model, warm)
    prefill(0, ids, 0, 32)
    blocks, wrote = warm.owned_blocks(0)
    assert warm.begin_sequence(1, (list(blocks[:4]), wrote[32]), 32, 64,
                               end=40)
    ids[:] = 0
    ids[:8] = tokens[32:40]
    tail = prefill(1, ids, 32, 40)
    np.testing.assert_array_equal(tail, whole)
    np.testing.assert_array_equal(
        np.asarray(warm.states[0].state.numpy())[:, :, 1],
        np.asarray(cold.states[0].state.numpy())[:, :, 0])


def test_a_nan_in_the_pad_rows_reaches_no_state_and_no_logit(tokens):
    """A real length that ends mid-bucket: the pad rows' token is one whose
    embedding is NaN, so every pad row of every layer is NaN.  The sampled
    row's logits are the reference's (but the NaN token's own column, which
    the tied head makes NaN for every row), the state and the snapshots stay
    finite, and decode goes on from them."""
    model, tree, d = seeded()
    emb = model.model.embed_tokens
    emb._set_data(emb._value().at[511].set(jnp.nan))
    want = reference_logits(tree, d, tokens[:30])
    # (the kernels: the jnp oracle multiplies a masked key's NaN by zero)
    cache = grouped(model, "pallas")
    assert cache.begin_sequence(0, None, 0, 32, end=21)
    ids = np.full(32, 511, np.int32)
    ids[:21] = tokens[:21]
    prefill, decode = compiled_steps(model, cache)
    got = prefill(0, ids, 0, 21)
    assert np.isnan(got[511])
    np.testing.assert_allclose(got[:511], want[20, :511], atol=2e-5, rtol=0)
    state = cache.states[0]
    assert np.isfinite(np.asarray(state.state.numpy())).all()
    assert np.isfinite(np.asarray(state.snapshots.numpy())).all()
    assert state.wrote(0).keys() == {16}
    for pos in range(21, 26):
        assert cache.ensure_capacity(0, pos)
        active, step = np.zeros(3, np.int32), np.full((3, 1), 511, np.int32)
        active[0], step[0, 0] = 1, tokens[pos]
        out, _counts = decode(step, active)
        np.testing.assert_allclose(out[0, 0, :511], want[pos, :511],
                                   atol=2e-5, rtol=0)
    # the idle slots decoded the NaN token: it reached no state of theirs
    assert np.isfinite(np.asarray(state.state.numpy())).all()


# -- (d) through the engine ------------------------------------------------------

def test_a_cold_prompt_and_its_decode_are_the_references(f32, tokens):
    model, tree, d = f32
    eng = engine(model, "pallas", buckets=(32,))
    h = eng.add_request(tokens[:30], max_new_tokens=24)
    eng.run()
    assert h.finished and not h.error
    greedy_matches(tree, d, tokens[:30], h.output_ids)
    st = eng.stats()
    assert st["state"]["prefills"] == 1 and \
        st["state"]["prefills_restored"] == 0
    assert st["state"]["slots"] == st["state"]["steps"] == 23
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_tail_behind_a_restored_snapshot_gives_a_cold_runs_tokens(
        small, tokens, cold):
    """A 64-token document served in two pieces, the second behind the
    snapshot the first left at its end; two questions behind the document at
    once both start from its snapshot and decode what cold runs decode."""
    model, tree, d = small
    doc = tokens[:64]
    q1 = np.concatenate([doc, tokens[64:76]])
    q2 = np.concatenate([doc, tokens[90:100]])
    c1 = cold.add_request(q1, max_new_tokens=20)
    c2 = cold.add_request(q2, max_new_tokens=20)
    cold.run()
    assert cold.stats()["state"]["groups"][0]["snapshots_written"] > 0
    eng = engine(model, buckets=(16, 32))
    for end in (32, 64):
        h = eng.add_request(doc[:end], max_new_tokens=1)
        eng.run()
        assert h.finished
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == 32
    assert eng.prefix_probe(q1) == 64 == eng.prefix_probe(q2)
    t0 = time.perf_counter()
    a = eng.add_request(q1, max_new_tokens=20)
    b = eng.add_request(q2, max_new_tokens=20)
    eng.step()
    assert len(eng.running) == 2
    eng.run()
    assert a.output_ids == c1.output_ids and b.output_ids == c2.output_ids
    greedy_matches(tree, d, q1, a.output_ids)
    st = eng.stats()
    assert st["paging"]["prefix"]["hit_tokens"] == 32 + 128
    assert st["state"]["prefills"] == 4 and \
        st["state"]["prefills_restored"] == 3
    assert st["state"]["hits_shortened"] == 0
    # the spans: both admissions started from the row of the document's end
    row = eng.prefix_cache.state_chains[0]._entries[
        eng.prefix_cache.chains[0]._keys_for(np.asarray(doc, np.int64), 8,
                                             b"")[7]].block_id
    pre = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.prefill"]
    assert [p["state_row"] for p in pre] == [row, row]
    assert [p["state_snapshots_written"] for p in pre] == [1, 1]   # 72
    assert all(p["state_hit_given_up"] == 0 for p in pre)
    steps = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.step"
             and "state_slots" in r[4]]
    assert steps and max(s["state_slots"] for s in steps) == 2
    rows = eng.cache.states[0].num_blocks - 1
    assert all(0 < s["state_snapshots_used"] <= s["state_snapshots"] == rows
               for s in steps)
    assert eng.health()["kv_block_invariants"] == "ok"


def test_two_cold_prompts_that_share_512_tokens_the_second_hits_512():
    """The default stride of 256 at block 16: the first prompt's prefill left
    snapshots at 256, 512 and at its last whole block; the second shares its
    first 512 tokens and differs after, so its hit ends at the stride's
    snapshot and it serves what a cold run serves."""
    model, tree, d = seeded(max_position_embeddings=1024)
    rng = np.random.default_rng(21)
    shared = rng.integers(0, 500, (512,), dtype=np.int32)
    p1 = np.concatenate([shared, rng.integers(0, 500, (88,), dtype=np.int32)])
    p2 = np.concatenate([shared, rng.integers(0, 500, (70,), dtype=np.int32)])
    kw = dict(num_slots=2, max_seq=1024, min_bucket=256, block_size=16,
              kernel="reference")
    eng = inference.create_engine(model, **kw)
    eng.warmup(buckets=[256, 1024])
    first = eng.add_request(p1, max_new_tokens=2)
    eng.run()
    assert eng.cache.states[0].stats()["snapshots_written"] == 3
    assert eng.prefix_probe(p2) == 512
    second = eng.add_request(p2, max_new_tokens=6)
    eng.run()
    assert first.finished and second.finished
    st = eng.stats()
    assert st["paging"]["prefix"]["hit_tokens"] == 512
    assert st["prefills_by_bucket"] == {1024: 1, 256: 1}
    assert st["state"]["prefills_restored"] == 1
    greedy_matches(tree, d, p2, second.output_ids)


def test_a_hit_is_shortened_to_where_a_snapshot_is_left(small, tokens, cold):
    """The K/V group has all 64 tokens of the document, the state group lost
    the snapshot at 64: the hit ends at 56, the longest length that has
    both; with none left it ends at 0; the tokens are the cold run's."""
    model, _tree, _d = small
    doc = tokens[:64]
    q = np.concatenate([doc, tokens[64:76]])
    c = cold.add_request(q, max_new_tokens=12)
    cold.run()
    eng = engine(model, buckets=(16, 32, 64, 128))
    eng.add_request(doc, max_new_tokens=1)
    eng.run()
    pc = eng.prefix_cache
    keys = pc.chains[0]._keys_for(np.asarray(q, np.int64), 8, b"")
    snaps = pc.state_chains[0]
    assert [k in snaps._entries for k in keys] == [
        False, True, False, True, False, True, True, True]   # 16 .. 48, 56, 64
    assert eng.prefix_probe(q) == 64
    snaps._evict_one(keys[7])
    assert eng.prefix_probe(q) == 56
    t0 = time.perf_counter()
    a = eng.add_request(q, max_new_tokens=12)
    eng.run()
    assert a.output_ids == c.output_ids
    (pre,) = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.prefill"]
    assert pre["state_hit_given_up"] == 8 and pre["state_row"] > 0
    st = eng.stats()
    assert st["state"]["hits_shortened"] == 1
    assert st["state"]["hit_tokens_given_up"] == 8
    assert st["paging"]["prefix"]["tokens_given_up"] == 8
    assert st["paging"]["prefix"]["hit_tokens"] == 56
    for key in list(snaps._entries):
        snaps._evict_one(key)
    assert eng.prefix_probe(q) == 0
    b = eng.add_request(q, max_new_tokens=12)
    eng.run()
    assert b.output_ids == c.output_ids
    # (the K/V group has the question's own 72 tokens by now)
    assert eng.stats()["state"]["hit_tokens_given_up"] == 8 + 72
    assert eng.health()["kv_block_invariants"] == "ok"


def test_the_tokens_given_up_are_this_admissions_own(small, tokens):
    """A lookup that raises is a plain miss: the span and the sums read 0
    given up, not what the cache's last sound lookup (of another request)
    gave up."""
    from paddle_tpu.distributed.fault_tolerance import ServingFaultPlan

    model, _tree, _d = small
    doc = tokens[:64]
    eng = engine(model, buckets=(16, 32, 64, 128))
    eng.add_request(doc, max_new_tokens=1)
    eng.run()
    pc = eng.prefix_cache
    q = np.concatenate([doc, tokens[64:76]])
    keys = pc.chains[0]._keys_for(np.asarray(q, np.int64), 8, b"")
    pc.state_chains[0]._evict_one(keys[7])
    eng.add_request(q, max_new_tokens=2)
    eng.run()
    assert pc.last_given_up == 8
    eng.fault_plan = ServingFaultPlan().add("serving.prefix_lookup",
                                            at_call=1)
    t0 = time.perf_counter()
    r = eng.add_request(np.concatenate([doc, tokens[80:90]]),
                        max_new_tokens=2)
    eng.run()
    assert r.finished and pc.last_given_up == 8
    (pre,) = [x[4] for x in _spans.snapshot(t0) if x[0] == "engine.prefill"]
    assert pre["state_row"] == 0 and pre["state_hit_given_up"] == 0
    assert eng.stats()["state"]["hit_tokens_given_up"] == 8


def test_the_snapshot_pool_evicts_its_oldest_idle_rows_first(small, tokens):
    """Five rows to give: the second document's snapshots take the first's,
    oldest first, and a question behind the first finds its blocks and no
    snapshot; one behind the second is restored."""
    model, _tree, _d = small
    eng = engine(model, buckets=(64,), num_state_snapshots=6)
    docs = [tokens[:64], tokens[64:128]]
    for doc in docs:
        h = eng.add_request(doc, max_new_tokens=1)
        eng.run()
        assert h.finished
    st = eng.stats()["state"]
    assert st["groups"][0]["snapshots_written"] == 10
    assert st["snapshot_evictions"] == 5
    assert eng.stats()["paging"]["prefix"]["snapshot_entries"] == [5]
    assert eng.prefix_probe(np.concatenate([docs[0], [1, 2, 3]])) == 0
    assert eng.prefix_probe(np.concatenate([docs[1], [1, 2, 3]])) == 64
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_preempted_request_resumes_from_its_own_prompts_snapshot(small,
                                                                   tokens):
    model, tree, d = small
    eng = engine(model, buckets=(8, 16, 64), num_slots=1, max_preemptions=2)
    prompt = tokens[:40]
    low = eng.add_request(prompt, max_new_tokens=30, priority=0)
    while len(low.output_ids) < 12:
        eng.step()
    so_far = list(low.output_ids)
    high = eng.add_request(tokens[100:110], max_new_tokens=4, priority=5)
    eng.run()
    assert low.preemptions == 1 and high.finished and low.finished
    assert low.output_ids[:len(so_far)] == so_far and len(low.output_ids) == 30
    greedy_matches(tree, d, prompt, low.output_ids)
    # the replay hit the prompt's last whole block: 32 of 40 tokens, by the
    # snapshot its own first prefill left there
    st = eng.stats()
    assert st["paging"]["prefix"]["hit_tokens"] == 32
    assert st["state"]["prefills_restored"] == 1
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_finished_slots_state_is_not_read_by_the_next_request(small, tokens):
    """One slot: what the first request left in it (here: NaN, planted after
    it finished) is not where the next one starts."""
    model, tree, d = small
    eng = engine(model, buckets=(32,), num_slots=1)
    eng.add_request(tokens[:30], max_new_tokens=8)
    eng.run()
    state = eng.cache.states[0].state
    state._set_data(jnp.full(state.shape, jnp.nan, state._value().dtype))
    nxt = eng.add_request(tokens[50:75], max_new_tokens=16)
    eng.run()
    assert nxt.finished and not nxt.error
    greedy_matches(tree, d, tokens[50:75], nxt.output_ids)
    assert np.isfinite(np.asarray(state.numpy())).all()


def test_bf16_engine_serves_within_a_tolerance():
    """bf16 weights, pool, state and snapshots through ``create_engine``,
    with four query heads a KV head (LFM2's own ratio): every
    greedy token's reference logit lies close under the reference's best,
    cold and behind a restored snapshot."""
    model, tree, d = seeded("bfloat16", num_attention_heads=8)
    eng = engine(model, "pallas", buckets=(16, 32))
    assert {str(b.dtype) for b in eng.cache.buffers()} == {"bfloat16"}
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 500, (30,), dtype=np.int32)
    for p in (prompt, np.concatenate([prompt[:24], prompt[:9]])):
        h = eng.add_request(p, max_new_tokens=30)
        eng.run()
        assert FAMILY.served_gap(tree, d, p, h.output_ids) < 0.05
    assert eng.stats()["state"]["prefills_restored"] == 1
    assert eng.health()["kv_block_invariants"] == "ok"


def test_the_snapshot_pools_size_is_refused_where_it_means_nothing():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    with pytest.raises(ValueError, match="num_state_snapshots: "
                       "GPTForCausalLM states no group that keeps state"):
        inference.create_engine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                                num_state_snapshots=8)


def test_a_block_that_does_not_divide_the_stride_is_refused():
    paddle.seed(0)
    with pytest.raises(ValueError, match="block_size 24 must divide the "
                       "snapshot stride 256"):
        inference.create_engine(lm.Lfm2ForCausalLM(lm.lfm2_tiny()),
                                num_slots=2, max_seq=96, min_bucket=24,
                                block_size=24)


# -- (e) the share's router ----------------------------------------------------

def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    cfg = FAMILY.tiny_config(num_experts=16, held_experts=[0, 16])
    d = REF.dims(cfg)
    lw = REF.layer_weights(
        weights.make(REF.weight_shapes(cfg), FAMILY.seed, jnp.float32), 1, d)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    chosen, w = REF.route(x, lw["moe.router"], lw["moe.bias"], d)
    plain, _ = REF.route(x, lw["moe.router"], 0 * lw["moe.bias"], d)
    assert (np.sort(np.asarray(chosen), 1)
            != np.sort(np.asarray(plain), 1)).any()
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-4)

"""The Mellum-2-shaped decoder on the paged engine (ISSUE 38): two kinds of
layer in one stack, a cache stated by layer — a full group that keeps every
token and a window group that lets its blocks go — a prefix hit of two kinds,
and the share of an expert-parallel deployment.  Everything is held against
``benchmarks/references/mellum.py`` (plain jnp, float32, imports nothing of
the program).  What goes through ``create_engine`` and is this family's alone
is in ``test_mellum_engine.py``."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.serving.group_cache import GroupedKVCache, GroupedPrefixCache
from paddle_tpu.serving.kv_cache import CacheGroup, CacheSpec

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, compiled_steps, f32, family, pytest_generate_tests,
    tokens, want,
    test_admission_waits_for_blocks,
    test_bf16_engine_serves_within_a_tolerance,
    test_full_forward_equals_the_reference,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype,
    test_the_shares_layer_outputs_add_up_to_the_uncut_layer)

FAMILY = FAMILIES["mellum"]
REF, mm = FAMILY.ref, FAMILY.models
W = 24                    # the tiny configuration's window: 3 blocks


def live(blocks):
    return [b for b in blocks if b]


# -- (a) the model, its statement, its two rotary tables ----------------------

def test_a_window_that_is_ignored_shows_in_the_reference(f32, tokens, want):
    """The reference's broken path (sliding layers attending over
    everything) moves the logits past the window and not before it."""
    _model, tree, d = f32
    h = REF.hidden(tree, jnp.asarray(tokens), d, window_off=True)
    off = np.asarray(REF.logits_rows(
        {k: tree[k] for k in REF.HEAD_KEYS}, h, d))
    np.testing.assert_allclose(off[:W], want[:W], atol=1e-4, rtol=0)
    assert np.abs(off[W + 8:] - want[W + 8:]).max() > 1e-2


def test_the_two_rotary_tables_are_the_references():
    c = mm.mellum_tiny()
    d = REF.dims(FAMILY.tiny_config())
    for kind in (mm.SLIDING, mm.FULL):
        inv, factor = mm.rotary_table(c, kind)
        freqs, m = REF.rotary_frequencies(d, kind)
        np.testing.assert_allclose(inv, freqs, rtol=1e-6)
        assert factor == pytest.approx(m)
    plain, yarn = mm.rotary_table(c, mm.SLIDING)[0], \
        mm.rotary_table(c, mm.FULL)[0]
    # the fast dimensions keep their frequency, the slow ones are / factor
    assert yarn[0] == plain[0] and yarn[-1] == pytest.approx(plain[-1] / 4)
    assert np.all(yarn <= plain) and np.any((yarn < plain)
                                            & (yarn > plain / 4 * 1.0001))
    # the published table: the ramp between pairs 18 and 35 of 64
    big = mm.MellumConfig()
    inv, factor = mm.rotary_table(big, mm.FULL)
    base = mm.rotary_table(big, mm.SLIDING)[0]
    assert factor == pytest.approx(1.2772588722239782)
    np.testing.assert_allclose(inv[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    assert np.all((base[19:35] / 16 < inv[19:35]) & (inv[19:35] < base[19:35]))


def test_the_published_layers_fall_into_a_full_and_a_window_group():
    assert FAMILY.spec().tail_limit == 2 * W
    big = mm.MellumForCausalLM.cache_spec(
        type("M", (), {"config": mm.MellumConfig()})())
    assert [(len(g.layers), g.window) for g in big.groups] == \
        [(7, 0), (21, 1024)]
    assert big.groups[0].layers == (3, 7, 11, 15, 19, 23, 27)
    with pytest.raises(ValueError, match="every layer"):
        CacheSpec.by_layer([CacheGroup((0, 2), ((2, 16), (2, 16)))])
    with pytest.raises(ValueError, match="layer_types"):
        mm.mellum_tiny(layer_types=("full_attention",)).kinds


@pytest.mark.parametrize("kind,groups", [
    ("kv", [(4, 0)]), ("latent", [(4, 0)]), ("indexed", [(4, 0)]),
    ("windowed", [(4, 32), (4, 0)])])
def test_every_kind_reads_as_groups(kind, groups):
    """The four kinds the repo had are cases of the same statement."""
    spec = {"kv": CacheSpec.kv(4, 2, 16), "latent": CacheSpec.latent(4, 80),
            "indexed": CacheSpec.indexed(4, 2, 16, 8, 24),
            "windowed": CacheSpec.windowed(4, 2, 16, 32, 4)}[kind]
    assert [(len(g.layers), g.window) for g in spec.groups] == groups
    assert all(g.sides == spec.sides for g in spec.groups)
    assert spec.tail_limit == (32 if kind == "windowed" else 0)


# -- (b) prefill, then decode, through the two-group cache: logits -------------

@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefill_then_decode_through_the_two_groups(f32, tokens, want,
                                                    kernel):
    """A cold 40-token prompt (longer than the window) in one program; a
    16-token tail that starts behind a window's worth of cached prefix, its
    window-group hit the last three blocks alone; then teacher-forced decode
    of both slots from 40 / 56 to 100 tokens, across many blocks' ends.
    Every logit row equals the reference's one full forward, and a
    window-group block is free exactly when the rule says."""
    model, _tree, d = f32
    spec = model.cache_spec()
    cache = GroupedKVCache(spec.groups, num_slots=3, max_seq=128,
                           dtype="float32", block_size=BLOCK,
                           num_blocks=[40, 20], kernel=kernel)
    full, win = cache.pools
    assert [tuple(b.shape) for b in full.buffers()] == [(40, BLOCK, 2, 128)] * 2
    assert [tuple(b.shape) for b in win.buffers()] == [(20, BLOCK, 2, 128)] * 6
    assert cache.nbytes() == (40 * 2 + 20 * 6) * BLOCK * 2 * 128 * 4

    prefill, decode = compiled_steps(model, cache)

    assert cache.begin_sequence(0, None, 0, 40, total=128)
    np.testing.assert_allclose(prefill(0, tokens[:40], 0, 40), want[39],
                               atol=2e-4, rtol=0)
    # behind the tail's first query's window only at its end: 40 - 23 = 17
    assert len(live(win.owned_blocks(0))) == 5
    assert cache.release_behind(0, 40) == 2
    assert win.owned_blocks(0)[:2] == [0, 0]
    assert cache.release_behind(0, 40) == 0             # never twice
    owned = cache.owned_blocks(0)
    hit = (list(owned[0]), [0, 0] + list(owned[1][2:]))
    assert cache.begin_sequence(2, hit, 40, 16, total=128)
    assert win.allocator.refcount(owned[1][2]) == 2     # shared, not copied
    np.testing.assert_allclose(prefill(2, tokens[40:56], 40, 56), want[55],
                               atol=2e-4, rtol=0)
    assert cache.release_behind(2, 56) == 2             # (56 - 23) // 8 = 4
    pos_of = {0: 40, 2: 56}
    while pos_of[2] < 100:
        active = np.zeros(3, np.int32)
        step = np.zeros((3, 1), np.int32)
        for s, pos in pos_of.items():
            cache.release_behind(s, pos)                 # the engine's order
            assert cache.ensure_capacity(s, pos)
            gone = max(0, pos - W + 1) // BLOCK
            assert win.owned_blocks(s)[:gone] == [0] * gone
            assert all(win.owned_blocks(s)[gone:])       # and never sooner
            assert all(full.owned_blocks(s))
            active[s], step[s, 0] = 1, tokens[pos]
        out, _counts = decode(step, active)
        for s, pos in pos_of.items():
            np.testing.assert_allclose(out[s, 0], want[pos], atol=2e-4,
                                       rtol=0)
        pos_of = {s: p + 1 for s, p in pos_of.items()}
    # slot 2 never held the two blocks its hit left out
    assert win.blocks_released == (83 - 23) // BLOCK + (99 - 23) // BLOCK - 2
    assert full.blocks_released == 0
    assert cache.check_invariants() == []
    cache.release_slot(0)
    cache.release_slot(2)
    assert [p.allocator.used_blocks for p in cache.pools] == [0, 0]
    assert [p.allocator.free_blocks for p in cache.pools] == [39, 19]


# -- (c) the groups: admission, release, copy-on-extend, the hit ---------------

def grouped(num_blocks=(30, 16)):
    sides = ((2, 16), (2, 16))
    cache = GroupedKVCache(
        [CacheGroup((1,), sides), CacheGroup((0,), sides, W)], num_slots=3,
        max_seq=128, dtype="float32", block_size=BLOCK,
        num_blocks=list(num_blocks))
    return cache, GroupedPrefixCache(cache)


def test_admission_takes_from_both_groups_or_from_neither():
    cache, _pc = grouped(num_blocks=(30, 7))            # 6 usable window blocks
    assert cache.begin_sequence(0, None, 0, 32, total=64)
    assert [p.allocator.free_blocks for p in cache.pools] == [25, 2]
    # the window group cannot give a second bucket: the full group's blocks
    # are handed back
    assert not cache.begin_sequence(1, None, 0, 32, total=64)
    assert [p.allocator.free_blocks for p in cache.pools] == [25, 2]
    assert cache.owned_blocks(1) == ([], []) and cache.deferred_by == [0, 1]
    # what the first may still grow by is kept for it: 4 more blocks of the
    # full group, none of the window group (3 + 1 held already)
    assert cache.growth_needs(0, 64) == [4, 0]
    assert not cache.begin_sequence(1, None, 0, 8, total=16,
                                    reserve=[25, 0])
    assert cache.begin_sequence(1, None, 0, 8, total=16, reserve=[4, 0])
    assert cache.check_invariants() == []


def test_copy_on_extend_in_both_groups():
    cache, _pc = grouped()
    assert cache.begin_sequence(0, None, 0, 16, total=32)
    shared = tuple(list(b) for b in cache.owned_blocks(0))
    assert cache.begin_sequence(1, shared, 16, 8, total=32)
    # position 12 lies in a block both slots hold: a private copy in each
    assert cache.ensure_capacity(1, 12)
    assert cache.copy_on_extends == 2
    for mine, theirs in zip(cache.owned_blocks(1), cache.owned_blocks(0)):
        assert mine[0] == theirs[0] and mine[1] != theirs[1]
    assert cache.check_invariants() == []


def test_the_hit_ends_where_both_groups_have_what_the_tail_reads():
    """Registered: 32 tokens (then the slot moved on), then 64.  The full
    group has 8 blocks; the window group the last window before 32 and the
    last before 64."""
    cache, pc = grouped()
    prompt = np.arange(100, dtype=np.int64)
    assert cache.begin_sequence(0, None, 0, 32, total=100)
    cache.release_behind(0, 32)                         # block 0 goes
    assert pc.register(prompt[:32], cache.owned_blocks(0)) == 4 + 3
    assert cache.extend_tail(0, 32, 32)
    cache.release_behind(0, 64)                         # blocks 1..4 go
    assert pc.register(prompt[:64], cache.owned_blocks(0)) == 4 + 3
    full, win = (c for c in pc.chains)
    assert (len(full), len(win), win.chained) == (8, 6, False)
    n, (f_ids, w_ids) = pc.lookup(prompt)
    assert n == 64 and len(f_ids) == 8 and all(f_ids)
    assert w_ids[:5] == [0] * 5 and all(w_ids[5:]) and len(w_ids) == 8
    assert pc.probe(prompt[:50]) == 32                  # capped by the prompt
    assert pc.hits_shortened == 0
    # the last window's first block gone: the longest end that has its
    # window is 32
    keys = full._keys_for(prompt, 8, b"")
    win._evict_one(keys[5])
    n, (f_ids, w_ids) = pc.lookup(prompt)
    assert n == 32 and len(f_ids) == 4 and w_ids == [0] + live(w_ids)
    assert pc.hits_shortened == 1
    # that window gone too: no end has one but the prompt's start
    win.clear()
    assert pc.lookup(prompt)[0] == 0 and pc.hits_shortened == 2
    # a cap on the end (the engine's, where a padded tail would pass the
    # table's end)
    cache.release_slot(0)
    assert pc.lookup(prompt, max_tokens=40)[0] == 0
    assert pc.stats()["group_entries"] == [8, 0]
    assert cache.check_invariants() == []


def test_a_window_groups_cache_is_evicted_oldest_first_and_all_of_it():
    """Every idle block of the window group is obtainable: its entries keep
    no chain."""
    cache, pc = grouped(num_blocks=(30, 9))
    prompt = np.arange(64, dtype=np.int64)
    assert cache.begin_sequence(0, None, 0, 64, total=64)
    pc.register(prompt, cache.owned_blocks(0))
    cache.release_slot(0)
    win = cache.pools[1]
    assert win.allocator.idle_cached_blocks == 8 == win.available_blocks()
    assert win.allocator.used_blocks == 0
    got = win.allocator.alloc(3)                        # evicts the oldest
    assert got is not None and win.allocator.idle_cached_blocks == 5
    assert pc.probe(np.arange(70)) == 64                # its end still stands
    assert win.allocator.check() == []


# -- (d) the engine: ``test_mellum_engine.py``; what it refuses --------------------

def test_the_groups_keywords_are_refused_where_they_mean_nothing():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    with pytest.raises(ValueError, match="num_window_blocks"):
        inference.create_engine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                                num_window_blocks=4)
    with pytest.raises(ValueError, match="num_summary_blocks"):
        inference.create_engine(mm.MellumForCausalLM(mm.mellum_tiny()),
                                num_slots=2, max_seq=64, min_bucket=8,
                                block_size=BLOCK, num_summary_blocks=4)

"""The Mellum-2-shaped decoder on the paged engine (ISSUE 38): two kinds of
layer in one stack, a cache stated by layer — a full group that keeps every
token and a window group that lets its blocks go — a prefix hit of two kinds,
and the share of an expert-parallel deployment.  Everything is held against
``benchmarks/references/mellum.py`` (plain jnp, float32, imports nothing of
the program)."""
import json
import os
import sys
import time

import numpy as np
import pytest
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu import inference                              # noqa: E402
from paddle_tpu.models import mellum as mm                    # noqa: E402
from paddle_tpu.obs import spans as _spans                    # noqa: E402
from paddle_tpu.serving.group_cache import (                  # noqa: E402
    GroupedKVCache, GroupedPrefixCache)
from paddle_tpu.serving.kv_cache import (                     # noqa: E402
    CacheGroup, CacheSpec, cache_spec_of)
from paddle_tpu.serving.paging import PagedCacheContext       # noqa: E402

from benchmarks.adapters import _load                         # noqa: E402
from benchmarks.harness import weights                        # noqa: E402
from benchmarks.harness.manifest import load_module           # noqa: E402

REF = load_module("references", "mellum")
ADAPTER = load_module("adapters", "mellum")
SEED = 2 ** 31 + 38
BLOCK, W = 8, 24          # the tiny configuration's window: 3 blocks


def tiny_config(**kw) -> dict:
    with open(os.path.join(ROOT, "tests", "benchmark_tests",
                           "tiny_mellum.json")) as f:
        return dict(json.load(f), **kw)


def seeded(dtype: str = "float32", **kw):
    """``(model, tree, d)``: the program's model holding the benchmark's
    seeded weights in ``dtype``; ``tree`` is what the reference reads."""
    cfg = tiny_config(torch_dtype=dtype, **kw)
    d = REF.dims(cfg)
    tree = weights.make(REF.weight_shapes(cfg), SEED, jnp.dtype(dtype))
    paddle.seed(0)
    model = ADAPTER.build_model(cfg)
    model.eval()
    _load.load(model, ADAPTER, tree, d)
    return model, tree, d


def reference_logits(tree, d, tokens):
    h = REF.hidden(tree, jnp.asarray(tokens), d)
    return np.asarray(REF.logits_rows({k: tree[k] for k in REF.HEAD_KEYS},
                                      h, d))


@pytest.fixture(scope="module")
def f32():
    return seeded()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(0, 512, (128,), dtype=np.int32)


@pytest.fixture(scope="module")
def want(f32, tokens):
    _model, tree, d = f32
    return reference_logits(tree, d, tokens)


def engine(model, kernel="pallas", buckets=(8, 16, 32), **kw):
    kw = dict(dict(num_slots=3, max_seq=128, min_bucket=8, block_size=BLOCK,
                   kernel=kernel), **kw)
    eng = inference.create_engine(model, **kw)
    eng.warmup(buckets=list(buckets))
    return eng


def greedy_matches(tree, d, prompt, out):
    """The served tokens are the reference's first choice wherever its best
    two logits are apart."""
    seq = np.concatenate([prompt, np.asarray(out)])
    lg = reference_logits(tree, d, seq)[len(prompt) - 1:-1]
    top2 = np.sort(lg, axis=-1)[:, -2:]
    sure = top2[:, 1] - top2[:, 0] > 1e-4
    assert sure.sum() >= len(out) // 2
    np.testing.assert_array_equal(np.asarray(out)[sure],
                                  lg.argmax(-1)[sure])


def live(blocks):
    return [b for b in blocks if b]


# -- (a) the model, its statement, its two rotary tables ----------------------

def test_full_forward_equals_the_reference_past_window_and_yarn(f32, tokens,
                                                                want):
    """128 tokens: five windows of 24 and twice YaRN's original length."""
    model, _tree, _d = f32
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value())[0]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)


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
    d = REF.dims(tiny_config())
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


def test_the_model_states_its_cache_by_layer_and_keeps_its_dtype():
    paddle.seed(0)
    model = mm.MellumForCausalLM(mm.mellum_tiny(dtype="bfloat16"))
    assert {str(p.dtype) for p in model.parameters()} == {"bfloat16"}
    spec = cache_spec_of(model)
    sides = ((2, 16), (2, 16))
    assert spec.kind == "kv" and spec.num_layers == 4
    assert spec.groups == (CacheGroup((3,), sides, 0),
                           CacheGroup((0, 1, 2), sides, W))
    assert spec.tail_limit == 2 * W
    big = mm.MellumForCausalLM.cache_spec(
        type("M", (), {"config": mm.MellumConfig()})())
    assert [(len(g.layers), g.window) for g in big.groups] == \
        [(7, 0), (21, 1024)]
    assert big.groups[0].layers == (3, 7, 11, 15, 19, 23, 27)
    with pytest.raises(ValueError, match="every layer"):
        CacheSpec.by_layer([CacheGroup((0, 2), sides)])
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

    def prefill(slot, ids, start, length):
        ctx = PagedCacheContext(
            cache, "prefill", slot=paddle.to_tensor(np.int32(slot)),
            length=paddle.to_tensor(np.int32(length)),
            start=paddle.to_tensor(np.int32(start)))
        out = model(paddle.to_tensor(ids[None]), cache_ctx=ctx)
        cache.set_length(slot, length)
        return np.asarray(out._value())[0, 0]

    from paddle_tpu import jit as jit_mod
    from paddle_tpu.core.autograd import no_grad

    def decode_step(step, act):
        ctx = PagedCacheContext(cache, "decode", active=act)
        out = model(step, cache_ctx=ctx)
        cache.advance(act)
        return out

    step_fn = jit_mod.to_static(decode_step)     # one program, as the engine

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
        with no_grad():
            out = np.asarray(step_fn(paddle.to_tensor(step),
                                     paddle.to_tensor(active))._value())
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


# -- (d) the engine -------------------------------------------------------------

def test_a_cold_prompt_longer_than_the_limit_goes_in_pieces(f32, tokens):
    """70 tokens against a tail limit of two windows (48): two programs
    inside one admission, the window group letting blocks go in between."""
    model, tree, d = f32
    eng = engine(model, buckets=(32, 48))
    assert eng.buckets == [8, 16, 32, 48]               # none above 2 windows
    t0 = time.perf_counter()
    h = eng.add_request(tokens[:70], max_new_tokens=30)
    eng.run()
    assert h.finished and not h.error
    greedy_matches(tree, d, tokens[:70], h.output_ids)
    rows = _spans.snapshot(t0)
    fills = [r[4] for r in rows if r[0] == "engine.prefill"]
    assert [a["bucket"] for a in fills] == [48, 32]
    i = np.arange(70)
    assert sum(a["swa_full_rows"] for a in fills) == int(np.sum(i + 1))
    assert sum(a["swa_window_rows"] for a in fills) == \
        int(np.sum(np.minimum(i + 1, W)))
    assert [(a["swa_full_keys"], a["swa_window_keys"]) for a in fills] == \
        [(48, 48), (70, 70 - (48 - W + 1))]
    steps = [r[4] for r in rows if r[0] == "engine.step"
             and "swa_context" in r[4]]
    assert len(steps) == 29
    for n, a in enumerate(steps):
        assert (a["swa_full_rows"], a["swa_window_rows"],
                a["swa_context"]) == (71 + n, W, 71 + n)
        assert a["swa_blocks"] == [p.num_blocks - 1 for p in eng.cache.pools]
        # a window's blocks, and the last bucket's pad block
        assert a["swa_blocks_used"][1] <= W // BLOCK + 2
    sw = eng.stats()["swa"]
    assert sw["steps"] == 29 and sw["context"] == sum(
        a["swa_context"] for a in steps)
    assert sw["blocks_released_prefill"] == (70 - W + 1) // BLOCK
    assert sw["blocks_released_prefill"] + sw["blocks_released_decode"] == \
        (99 - W + 1) // BLOCK
    assert [g["window"] for g in sw["groups"]] == [0, W]
    assert sw["deferred_by_group"] == [0, 0]
    assert eng.stats()["paging"]["groups"][1]["released"] == 9
    assert eng.stats()["compile_cache"]["misses"] == 2 + 1
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_prefix_hit_of_two_kinds_gives_a_cold_runs_tokens(f32, tokens):
    """A 64-token document served once; a question behind it hits all 64
    tokens — every block of the full group, the last window's of the window
    group — and decodes what the cold run decoded; two questions behind it
    at once diverge; with the window's blocks gone the hit is shortened (to
    nothing: no end has a window left) and the tokens are still the cold
    run's."""
    model, tree, d = f32
    doc = tokens[:64]
    q1 = np.concatenate([doc, tokens[64:76]])
    q2 = np.concatenate([doc, tokens[90:100]])
    cold = engine(model, enable_prefix_cache=False)
    c1 = cold.add_request(q1, max_new_tokens=20)
    c2 = cold.add_request(q2, max_new_tokens=20)
    cold.run()
    eng = engine(model)
    pc = eng.prefix_cache
    assert isinstance(pc, GroupedPrefixCache)
    first = eng.add_request(doc[:32], max_new_tokens=1)
    eng.run()
    second = eng.add_request(doc, max_new_tokens=1)
    eng.run()
    assert first.finished and second.finished
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == 32
    assert eng.prefix_probe(q1) == 64
    a = eng.add_request(q1, max_new_tokens=20)
    b = eng.add_request(q2, max_new_tokens=20)
    eng.step()
    assert len(eng.running) == 2                        # side by side
    # both hold the document's blocks: every one of the full group, and of
    # the window group what is left of the last window's three (block 5
    # went with the tail's end: 76 - 23 = 53)
    for slot in (a.slot, b.slot):
        f_ids, w_ids = eng.cache.owned_blocks(slot)
        assert len(live(f_ids)) >= 8
        assert w_ids[:6] == [0] * 6 and all(w_ids[6:8])
    assert eng.cache.owned_blocks(a.slot)[0][:8] == \
        eng.cache.owned_blocks(b.slot)[0][:8]
    eng.run()
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == 32 + 128
    assert a.output_ids == c1.output_ids and b.output_ids == c2.output_ids
    greedy_matches(tree, d, q1, a.output_ids)
    # the document's last window gone from the cache (and the run of the 32
    # tokens first served dropped when the document moved a window past
    # it): the hit ends where a window's blocks are left, at the first block
    win = pc.chains[1]
    for key in win._keys_for(np.asarray(q1, np.int64), 8, b"")[4:]:
        if key in win._entries:
            win._evict_one(key)
    assert eng.prefix_probe(q1) == 0
    again = eng.add_request(q1, max_new_tokens=20)
    eng.run()
    assert again.output_ids == c1.output_ids
    st = eng.stats()
    assert st["swa"]["hits_shortened"] >= 1
    assert st["paging"]["prefix"]["hit_tokens"] == 32 + 128
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_tail_behind_a_hit_reports_the_prefill_kernels_work_items(f32,
                                                                    tokens):
    """A 9-token tail behind a 64-token cached document, in the 16 bucket:
    the ``engine.prefill`` span carries, for one layer of each kind, the
    work items of the tail-prefill kernel's own list on the same inputs and
    the rows it multiplies against the rows asked for; ``stats()`` sums
    them."""
    from paddle_tpu.ops.pallas import paged_attention_kernel as pk

    model, _tree, _d = f32
    eng = engine(model)
    doc = tokens[:64]
    eng.add_request(doc, max_new_tokens=1)
    eng.run()
    before = dict(eng.stats()["swa"])
    t0 = time.perf_counter()
    h = eng.add_request(np.concatenate([doc, tokens[64:73]]),
                        max_new_tokens=2)
    eng.step()                                # the admission and its prefill
    rows = [np.asarray(ids) for ids in eng.cache.owned_blocks(h.slot)]
    eng.run()
    assert h.finished and not h.error
    (a,) = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.prefill"]
    assert (a["bucket"], a["swa_full_keys"]) == (16, 73)
    c = model.config
    want = {"prefill_real_rows": 9, "prefill_items_run": 0}
    for pool, key, ids in zip(eng.cache.pools, ("prefill_items_full",
                                                "prefill_items_window"),
                              rows):
        _, bs, hkv, lanes = pool.sides[0][0].shape
        mb = pool.max_blocks_per_slot
        ts, ct = pk.prefill_plan(16, hkv, c.num_attention_heads // hkv,
                                 lanes, 4, bs, mb)
        tile, chunk, n = pk.prefill_work_list(
            jnp.int32(64), jnp.int32(73), S=16, tile=ts, chunk_tokens=ct,
            window=pool.kv_window, places=pk.prefill_places(
                16, ts, ct, mb, bs, pool.kv_window))
        want[key] = int(n)
        want.setdefault("prefill_tile_rows", -(-9 // ts) * ts)
        # the items whose chunk the kernel takes in one copy: every block of
        # the chunk holds a key the tile reads (none behind the first row's
        # window, none past the last real row) and their ids are consecutive
        cb = ct // bs
        for t, ch in zip(np.asarray(tile)[:int(n)], np.asarray(chunk)):
            lo = max(0, 64 + t * ts - pool.kv_window + 1) \
                if pool.kv_window else 0
            hi = min(64 + (t + 1) * ts, 73) - 1
            blocks = ids[ch * cb:(ch + 1) * cb]
            want["prefill_items_run"] += int(
                len(blocks) == cb and lo // bs <= ch * cb
                and hi // bs >= (ch + 1) * cb - 1
                and (np.diff(blocks) == 1).all())
    # (a row of 128 positions is one chunk, which the tail's end cuts: none
    # here; ``tests/test_serving_admission.py`` counts at the cells' shapes)
    assert want["prefill_items_run"] == 0
    assert want["prefill_items_full"] >= want["prefill_items_window"] >= 1
    assert want["prefill_tile_rows"] >= 9
    assert {k: a[k] for k in want} == want
    after = eng.stats()["swa"]
    assert {k: after[k] - before[k] for k in want} == want
    # the reference path has no work list: its spans carry none
    ref = engine(model, kernel="reference")
    t0 = time.perf_counter()
    ref.add_request(doc[:20], max_new_tokens=1)
    ref.run()
    (b,) = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.prefill"]
    assert "swa_full_rows" in b and "prefill_items_full" not in b
    assert ref.stats()["swa"]["prefill_items_full"] == 0


def test_documents_made_resident_in_pieces_keep_their_last_windows(f32):
    """Three documents of 96 tokens, each served in growing pieces of 32 (as
    the resident driver does), through a window group too small for a window
    a piece (9 x 4 blocks) but not for a window a document: the run a piece
    hit is dropped once the piece has registered its own, a whole window
    on, so the oldest document's last window is not the first to go, and a
    question behind each document hits all of it."""
    model, tree, d = f32
    eng = engine(model, num_slots=2, num_kv_blocks=60, num_window_blocks=20)
    rng = np.random.default_rng(11)
    docs = [rng.integers(0, 512, (96,), dtype=np.int32) for _ in range(3)]
    for doc in docs:
        for end in (32, 64, 96):
            h = eng.add_request(doc[:end], max_new_tokens=1)
            eng.run()
            assert h.finished
    win = eng.prefix_cache.chains[1]
    # a document's last window is (96 - 8 - 23) // 8 = 8 .. 11: 4 blocks
    assert len(win) == 3 * 4 and win.evictions == 3 * 2 * 4
    assert eng.stats()["swa"]["hits_shortened"] == 0
    for doc in docs:
        q = np.concatenate([doc, rng.integers(0, 512, (9,), dtype=np.int32)])
        assert eng.prefix_probe(q) == 96
        h = eng.add_request(q, max_new_tokens=6)
        eng.run()
        greedy_matches(tree, d, q, h.output_ids)
    # a question's run overlaps its document's: nothing was dropped for it
    assert win.evictions == 3 * 2 * 4
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == \
        3 * (32 + 64) + 3 * 96
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_preempted_request_resumes_through_the_hit(f32, tokens):
    model, tree, d = f32
    eng = engine(model, buckets=(8, 16, 32, 48), num_slots=1,
                 max_preemptions=2)
    prompt = tokens[:40]
    low = eng.add_request(prompt, max_new_tokens=30, priority=0)
    while len(low.output_ids) < 12:
        eng.step()
    assert eng.cache.check_invariants() == []
    so_far = list(low.output_ids)
    high = eng.add_request(tokens[100:110], max_new_tokens=4, priority=5)
    eng.run()
    assert low.preemptions == 1 and high.finished and low.finished
    assert low.output_ids[:len(so_far)] == so_far and len(low.output_ids) == 30
    greedy_matches(tree, d, prompt, low.output_ids)
    # the prompt's whole blocks but the last token's: 4 of 5
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] >= 32
    assert eng.health()["kv_block_invariants"] == "ok"


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_waits_for_blocks_of_either_group(f32, tokens, short):
    """With one group sized for one sequence's life only, the second request
    is deferred, not failed, and is served when the first retires."""
    model, _tree, _d = f32
    sizes = dict(full=dict(num_kv_blocks=14, num_window_blocks=40),
                 window=dict(num_kv_blocks=60, num_window_blocks=8))[short]
    eng = engine(model, buckets=(8, 32), num_slots=2, **sizes)
    a = eng.add_request(tokens[:30], max_new_tokens=60)
    b = eng.add_request(tokens[10:40], max_new_tokens=60)
    eng.step()
    assert len(eng.running) == 1 and len(eng.queue) == 1
    eng.run()
    assert a.finished and b.finished and not a.error and not b.error
    st = eng.stats()
    assert st["failures"]["failed"] == 0
    by = st["swa"]["deferred_by_group"]
    assert by[short == "window"] > 0 and by[short == "full"] == 0
    assert eng.health()["kv_block_invariants"] == "ok"


@pytest.mark.parametrize("heads", [4, 16])
def test_bf16_engine_serves_within_a_tolerance(heads):
    """bf16 weights and pools through ``create_engine``: every greedy token's
    reference logit lies close under the reference's best — with two query
    heads a KV head (the decode kernel's row-at-a-time form) and with eight
    (its matmul form)."""
    model, tree, d = seeded("bfloat16", num_attention_heads=heads)
    eng = engine(model, buckets=(32,))
    assert {str(b.dtype) for b in eng.cache.buffers()} == {"bfloat16"}
    prompt = np.random.default_rng(3).integers(0, 512, (30,), dtype=np.int32)
    h = eng.add_request(prompt, max_new_tokens=40)
    eng.run()
    seq = np.concatenate([prompt, np.asarray(h.output_ids)])
    lg = reference_logits(tree, d, seq)[len(prompt) - 1:-1]
    gap = lg.max(-1) - np.take_along_axis(
        lg, np.asarray(h.output_ids)[:, None], axis=-1)[:, 0]
    assert gap.max() < 0.05
    assert eng.health()["kv_block_invariants"] == "ok"


def _refusals():
    from paddle_tpu.serving.sharding import serving_mesh
    from paddle_tpu.serving.spec_decode import SpecConfig

    draft = mm.MellumForCausalLM(mm.mellum_tiny())
    return {"mesh": (dict(mesh=serving_mesh(2)),
                     r"a serving mesh of more than one device \(the groups' "
                     r"tables are not sharded\)"),
            "speculation": (
                dict(speculation=SpecConfig(draft_model=draft, k=2)),
                r"speculation= \(the verify window has no by-layer form\)")}


@pytest.mark.parametrize("what", ["mesh", "speculation"])
def test_a_cache_stated_by_layer_refuses_what_it_has_no_form_for(what):
    paddle.seed(0)
    model = mm.MellumForCausalLM(mm.mellum_tiny())
    kw, msg = _refusals()[what]
    with pytest.raises(ValueError, match="MellumForCausalLM caches K and V "
                       "by groups of layers, some only inside a window and "
                       "cannot serve with " + msg):
        inference.create_engine(model, num_slots=2, max_seq=64,
                                min_bucket=8, block_size=BLOCK, **kw)


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


# -- (e) the share: four chips' layers add up to the uncut layer ---------------

def test_the_shares_layer_outputs_add_up_to_the_uncut_layer():
    """16 experts, 4 shares of 4: each share routes over all 16, renormalises
    over the 4 chosen and computes its own; the four outputs sum to the
    reference's layer with every expert held."""
    from paddle_tpu.models.keye_vl2 import KeyeVL2MoE

    cfg = tiny_config(num_experts=16, held_experts=[0, 16])
    d = REF.dims(cfg)
    tree = weights.make(REF.weight_shapes(cfg), SEED, jnp.float32)
    lw = REF.layer_weights(tree, 1, d)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    whole = np.asarray(REF.experts(x, lw, d, False))
    total = np.zeros_like(whole)
    parts = []
    for share in range(4):
        held = (4 * share, 4 * share + 4)
        paddle.seed(0)
        layer = KeyeVL2MoE(ADAPTER.program_config(
            dict(cfg, held_experts=list(held))))
        layer.gate._set_data(lw["moe.router"])
        layer.experts_gate_up._set_data(jnp.concatenate(
            [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)[held[0]:held[1]])
        layer.experts_down._set_data(lw["moe.w_down"][held[0]:held[1]])
        y = np.asarray(layer(x[None])[0])
        np.testing.assert_allclose(
            y, np.asarray(REF.experts(x, lw, d, False, held=held)),
            atol=2e-5)
        parts.append(y)
        total += y
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert all(np.abs(p).max() > 1e-4 for p in parts)    # each share adds

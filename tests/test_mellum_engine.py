"""The Mellum-2-shaped decoder through the paged engine (ISSUE 38): a prompt
longer than the tail limit, a prefix hit of two kinds, the tail-prefill
kernel's work items on the spans, documents made resident in pieces,
preemption.  Beside ``test_mellum.py`` (the model, its two-group cache and the
common cases), so that the two spread over two workers; they share the seeded
model's few seconds and nothing else."""
import time

import numpy as np
import jax.numpy as jnp

from paddle_tpu.obs import spans as _spans
from paddle_tpu.serving.group_cache import GroupedPrefixCache

from families import BLOCK, FAMILIES, f32, family, tokens  # noqa: F401

FAMILY = FAMILIES["mellum"]
engine, greedy_matches = FAMILY.engine, FAMILY.greedy_matches
W = 24                    # the tiny configuration's window: 3 blocks


def live(blocks):
    return [b for b in blocks if b]


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
    cold = engine(model, buckets=(32, 48), enable_prefix_cache=False)
    c1 = cold.add_request(q1, max_new_tokens=20)
    c2 = cold.add_request(q2, max_new_tokens=20)
    cold.run()
    eng = engine(model, buckets=(16, 32, 48))
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
    eng = engine(model, buckets=(16, 48))
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
    ref = engine(model, kernel="reference", buckets=(32,))
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
    eng = engine(model, buckets=(16, 32), num_slots=2, num_kv_blocks=60,
                 num_window_blocks=20)
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
    eng = engine(model, buckets=(8, 16, 48), num_slots=1, max_preemptions=2)
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

"""The Keye-VL-2.0-shaped decoder (GQA under a learned indexer over a
three-sided paged pool, three-stream rotary, softmax-routed held experts) at
a tiny preset (hidden 64, 4 query / 2 KV heads of 16, indexer 4 heads of 8
with ``topk`` 24, 16 experts top-4 of which 4 held, 3 layers, vocabulary
512), against the benchmark's plain reference
(``benchmarks/references/keye_vl2.py``: float32, a selection mask per query,
a loop over the held experts; it imports nothing of the program)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.obs import spans as _spans
from paddle_tpu.ops.pallas import dsa_attention_kernel as dsa
from paddle_tpu.serving.paging import PagedKVCache

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, compiled_steps, f32, family, tokens,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype)

from benchmarks.harness import weights                        # noqa: E402

FAMILY = FAMILIES["keye_vl2"]
REF, km, SEED = FAMILY.ref, FAMILY.models, FAMILY.seed
seeded, reference_logits = FAMILY.seeded, FAMILY.reference_logits
tiny_config = FAMILY.tiny_config
TOPK = 24


# -- (a) float32 against the reference ---------------------------------------

@pytest.mark.parametrize("topk", [TOPK, 256])
def test_full_forward_equals_the_reference(tokens, topk):
    """With the selection active (``topk`` 24 of up to 72 tokens) and not
    (``topk`` past the sequence: every query keeps its whole context)."""
    cfg = tiny_config()
    cfg["sa_config"] = dict(cfg["sa_config"], topk=topk)
    model, tree, d = seeded("float32", sa_config=cfg["sa_config"])
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value())[0]
    want = reference_logits(tree, d, tokens)
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    dense = reference_logits(tree, d, tokens,
                             select=lambda idx, k: idx > -jnp.inf)
    assert (np.abs(want - dense).max() > 1e-3) == (topk == TOPK)


def test_unequal_position_streams_reach_the_rotary(f32):
    """What a vision tower would feed: input embeddings with the three
    position streams apart (a 4 x 5 grid of image tokens between text)."""
    model, tree, d = f32
    rng = np.random.default_rng(3)
    S = 40
    embeds = rng.normal(0, 0.02, (S, d["hidden"])).astype(np.float32)
    pos3 = np.broadcast_to(np.arange(S), (3, S)).copy()
    pos3[0, 8:28] = 8                                   # one frame
    pos3[1, 8:28] = 8 + np.repeat(np.arange(4), 5)      # rows
    pos3[2, 8:28] = 8 + np.tile(np.arange(5), 4)        # columns
    pos3[:, 28:] = pos3[:, 28:] - 28 + 13
    got = np.asarray(model(inputs_embeds=embeds[None],
                           position_ids=pos3[:, None, :])._value())[0]
    h = REF.hidden_embeds(tree, embeds, pos3, d)
    want = np.asarray(REF.logits_rows(
        {k: tree[k] for k in REF.HEAD_KEYS}, h, d))
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    equal = np.asarray(model(inputs_embeds=embeds[None])._value())[0]
    assert np.abs(equal - got).max() > 1e-3


@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefix_tail_prefill_and_decode_through_the_indexed_pool(
        f32, tokens, kernel):
    """Slot 0 prefills a 16-token prefix (the dense path: under ``topk``);
    slot 2 shares its two blocks and prefills the 21-token tail behind them
    (bucket 32, 37 tokens: the indexed path); then it decodes
    (teacher-forced) to 48 tokens with slot 1 idle on the scratch block, and
    slot 0 decodes from 16 tokens *across* ``topk``: the step is dense while
    every running slot is under it and indexed once one is past it.  Every
    logit row the engine would sample from equals the reference's full
    forward."""
    model, tree, d = f32
    want = reference_logits(tree, d, tokens)
    cache = PagedKVCache(num_slots=3, num_layers=d["layers"], max_seq=64,
                         sides=model.cache_spec().sides, block_size=BLOCK,
                         kernel=kernel)
    assert [tuple(b.shape) for b in cache.buffers()] == \
        [(25, BLOCK, 2, 128)] * 2 * d["layers"] \
        + [(25, BLOCK, 1, 128)] * d["layers"]     # a buffer a layer a side

    prefill, decode_step = compiled_steps(model, cache,
                                          counts="selection_counts")

    def decode(slots, pos_of):
        active = np.zeros(3, np.int32)
        step = np.zeros((3, 1), np.int32)
        for s in slots:
            assert cache.ensure_capacity(s, pos_of[s])
            active[s], step[s, 0] = 1, tokens[pos_of[s]]
        out, counts = decode_step(step, active)
        for s in slots:
            np.testing.assert_allclose(out[s, 0], want[pos_of[s]],
                                       atol=1e-4, rtol=0)
        assert len(counts) == d["layers"]
        return counts

    assert cache.begin_sequence(0, [], 0, 16)
    np.testing.assert_allclose(prefill(0, tokens[:16], 0, 16), want[15],
                               atol=1e-4, rtol=0)
    shared = list(cache.owned_blocks(0))
    assert cache.begin_sequence(2, shared, 16, 32)
    tail = np.zeros(32, np.int32)
    tail[:21] = tokens[16:37]
    np.testing.assert_allclose(prefill(2, tail, 16, 37), want[36],
                               atol=1e-4, rtol=0)
    # slot 2 alone, past topk: every layer selects topk of its context (and
    # what ties with the cut: at these widths a score is exactly 0 whenever
    # all four indexer heads are negative)
    for pos in range(37, 40):
        counts = decode([2], {2: pos})
        assert all(TOPK <= sel < ctx == pos + 1 for sel, ctx in counts)
    # slot 0 alone, under topk: the dense step (selected == context); its
    # first token copies the block it shares with slot 2 on extend
    before = cache.copy_on_extends
    for pos in range(16, 18):
        assert decode([0], {0: pos}) == [(pos + 1, pos + 1)] * d["layers"]
    assert cache.copy_on_extends == before
    # both: one slot past topk makes the step indexed for both, and slot 0
    # crosses topk on the way (up to 24 cached tokens keep all, then 24 of 25)
    for i in range(8):
        counts = decode([0, 2], {0: 18 + i, 2: 40 + i})
        assert all(min(19 + i, TOPK) + TOPK <= sel < ctx == 60 + 2 * i
                   for sel, ctx in counts)
    assert cache.check_invariants() == []


def test_copy_on_extend_carries_all_three_sides(f32, tokens):
    """Two slots share a partly filled last block; the one that appends gets
    its own copy of the block in every side's buffer, indexer keys too."""
    model, _tree, d = f32
    cache = PagedKVCache(num_slots=2, num_layers=d["layers"], max_seq=64,
                         sides=model.cache_spec().sides, block_size=BLOCK)
    assert cache.begin_sequence(0, [], 0, 16)
    marks = [float(i + 1) for i in range(len(cache.buffers()))]
    blk = cache.owned_blocks(0)[1]
    for buf, m in zip(cache.buffers(), marks):
        buf._set_data(buf._value().at[blk].set(m))
    cache.allocator.ref(blk)                  # a second holder
    cache._slot_blocks[1] = [cache.owned_blocks(0)[0], blk]
    cache.allocator.ref(cache.owned_blocks(0)[0])
    assert cache.ensure_capacity(1, 12)       # appends into the shared block
    fresh = cache.owned_blocks(1)[1]
    assert fresh != blk and cache.copy_on_extends == 1
    for buf, m in zip(cache.buffers(), marks):
        assert float(jnp.min(buf._value()[fresh])) == m
        assert float(jnp.max(buf._value()[fresh])) == m


# -- (b) the kernels against their oracles ------------------------------------

def _pools(rng, dtype=jnp.float32):
    NB, bs, hkv, D = 40, 8, 2, 128
    kp, vp = (jnp.asarray(rng.normal(size=(NB, bs, hkv, D)), dtype)
              for _ in range(2))
    ip = jnp.asarray(rng.normal(size=(NB, bs, D)), dtype)
    tables = jnp.asarray(rng.permutation(NB - 1)[:24].reshape(3, 8) + 1,
                         jnp.int32)
    return kp, vp, ip, tables


@pytest.mark.parametrize("runs", [False, True])
def test_index_scores_kernel_equals_its_oracle_on_the_live_chunks(runs):
    """Block by block through a shuffled table, and in one copy a chunk
    where a slot's blocks lie one after another in the pool."""
    rng = np.random.default_rng(0)
    _kp, _vp, ip, tables = _pools(rng)
    if runs:
        tables = jnp.asarray(np.arange(24).reshape(3, 8) + 5, jnp.int32)
        assert np.asarray(dsa._whole_runs(
            tables, jnp.arange(3), jnp.zeros(3, jnp.int32), 8)).all()
    q = jnp.asarray(rng.normal(size=(3, 2 * 4, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 2 * 4)), jnp.float32)
    last = jnp.asarray([5, 40, 63], jnp.int32)
    active = jnp.asarray([1, 1, 0], jnp.int32)
    got = np.asarray(dsa.index_scores(q, w, ip, tables, last, active,
                                      heads=4, interpret=True))
    want = np.asarray(dsa.index_scores_reference(q, w, ip, tables, heads=4))
    assert got.shape == want.shape == (3, 2, 64)
    for g, n in ((0, 6), (1, 41)):            # what the caller may read
        np.testing.assert_allclose(got[g, :, :n], want[g, :, :n], atol=1e-5)


@pytest.mark.parametrize("ties", [0, 5, 40])
def test_the_searched_cut_and_the_selected_rows_keep_every_tie(ties):
    """The cut equals the sort's, and the list holds exactly the positions at
    or above it in order — with ``ties`` entries tied at the cut, all kept
    (up to the list's room)."""
    rng = np.random.default_rng(ties)
    B, T, k = 4, 256, 16
    z = rng.normal(size=(B, T)).astype(np.float32)
    last = np.asarray([255, 100, 9, 200])
    if ties:
        for b in range(B):
            kth = np.sort(z[b, :last[b] + 1])[::-1][min(k, last[b] + 1) - 1]
            spots = rng.permutation(last[b] + 1)[:ties]
            z[b, spots] = np.where(z[b, spots] < kth, kth, z[b, spots])
    visible = np.arange(T)[None, :] <= last[:, None]
    keys, cut = dsa.selection_cut(jnp.asarray(z), jnp.asarray(visible), k)
    want_cut = np.asarray(dsa.selection_cut_reference(
        jnp.asarray(z), jnp.asarray(visible), k))
    from paddle_tpu.ops.threshold_search import key_values
    np.testing.assert_array_equal(np.asarray(key_values(cut)), want_cut)
    mask = visible & (np.asarray(keys) >= np.asarray(cut)[:, None])
    np.testing.assert_array_equal(mask, visible & (z >= want_cut[:, None]))
    cap = k + 8
    idx, n = (np.asarray(a) for a in dsa.select_rows(jnp.asarray(mask), cap))
    for b in range(B):
        want = np.flatnonzero(mask[b])
        assert n[b] == min(len(want), cap) >= min(k, last[b] + 1)
        np.testing.assert_array_equal(idx[b, :n[b]], want[:cap])
    if ties == 5:
        assert (mask.sum(1) > k).any()        # ties really were kept


@pytest.mark.parametrize("kernel_dtype", ["float32", "bfloat16"])
def test_sparse_decode_and_prefill_kernels_equal_their_oracles(kernel_dtype):
    rng = np.random.default_rng(1)
    dt = jnp.dtype(kernel_dtype)
    kp, vp, ip, tables = _pools(rng, dt)
    tol = 1e-5 if kernel_dtype == "float32" else 3e-2
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), dt)
    qi = jnp.asarray(rng.normal(size=(3, 4, 128)), dt)
    w = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    lengths = jnp.asarray([5, 40, 63], jnp.int32)
    active = jnp.asarray([1, 1, 0], jnp.int32)
    outs = [dsa.indexed_decode(q, qi, w, kp, vp, ip, tables, lengths, active,
                               topk=16, heads=4, scale=0.25, kernel=kern,
                               interpret=True)
            for kern in ("reference", "pallas")]
    np.testing.assert_allclose(np.asarray(outs[0][0], np.float32),
                               np.asarray(outs[1][0], np.float32), atol=tol)
    if kernel_dtype == "float32":
        np.testing.assert_array_equal(np.asarray(outs[1][1]), [6, 16, 0])
    assert not np.asarray(outs[1][0], np.float32)[2].any()   # idle: zero
    qs = jnp.asarray(rng.normal(size=(16, 4, 128)), dt)
    qis = jnp.asarray(rng.normal(size=(16, 4, 128)), dt)
    ws = jnp.asarray(rng.normal(size=(16, 4)), jnp.float32)
    for start, length in ((0, 13), (32, 45), (48, 64)):
        a, b = (np.asarray(dsa.indexed_prefill(
            qs, qis, ws, kp, vp, ip, tables[1], start, length, topk=16,
            heads=4, scale=0.25, kernel=kern, interpret=True), np.float32)
            for kern in ("reference", "pallas"))
        real = length - start
        np.testing.assert_allclose(a[:real], b[:real], atol=tol)


@pytest.mark.parametrize("table", ["shuffled", "runs", "mixed"])
@pytest.mark.parametrize("start,real", [
    (512, 100),      # two tiles of 64 queries, the last part padding
    (0, 128),        # cold: a tile's list ends in the chunk it lies in
    (256, 7),        # one real tile of two: the other is never visited
])
def test_the_tail_kernel_under_a_selection_keeps_every_tie(table, start,
                                                            real):
    """``sparse_prefill`` (the K/V tail-prefill kernel with the selection in
    its mask) against the masked oracle, on chunks of 256 keys that come in
    one copy (``runs``), block by block (``shuffled``) or both (``mixed``):
    scores drawn from five values, so that many keys tie at a query's cut and
    all are kept; the rows past the real length — whose scores, in a group no
    query of which is real, are whatever the buffer held: NaN here — come out
    zero."""
    rng = np.random.default_rng(start + real)
    NB, bs, hkv, rep, D, mb, S, topk = 130, 8, 2, 2, 128, 96, 128, 48
    kp, vp = (jnp.asarray(rng.normal(size=(NB, bs, hkv, D)), jnp.float32)
              for _ in range(2))
    row = np.arange(1, mb + 1)
    if table != "runs":
        for c in range(0 if table == "shuffled" else 1, mb // 32, 2):
            row[c * 32:(c + 1) * 32] = rng.permutation(row[c * 32:(c + 1) * 32])
    if table == "shuffled":
        row = rng.permutation(row)
    assert np.asarray(dsa._whole_runs(
        jnp.asarray(row[None]), jnp.zeros(3, jnp.int32), jnp.arange(3), 32)
        ).tolist() == {"runs": [1, 1, 1], "mixed": [1, 0, 1],
                       "shuffled": [0, 0, 0]}[table]
    T = mb * bs
    q = jnp.asarray(rng.normal(size=(S, hkv * rep, D)), jnp.float32)
    scores = rng.integers(0, 5, (S, T)).astype(np.float32)
    qpos = start + np.arange(S)
    visible = np.arange(T)[None, :] <= qpos[:, None]
    cut = np.asarray(dsa.selection_cut_reference(
        jnp.asarray(scores), jnp.asarray(visible), topk))
    selected = visible & (scores >= cut[:, None])
    kept = selected[:real].sum(1)
    assert (kept >= np.minimum(qpos[:real] + 1, topk)).all()
    assert (kept > topk).any()                # ties at the cut, all kept
    want = np.asarray(dsa.masked_prefill_reference(
        q, kp, vp, jnp.asarray(row, jnp.int32), jnp.asarray(selected),
        scale=0.25))
    scores[-(-real // 32) * 32:] = np.nan     # groups the indexer never wrote
    got = np.asarray(dsa.sparse_prefill(
        q, kp, vp, jnp.asarray(row, jnp.int32), start, start + real,
        jnp.asarray(scores), jnp.asarray(cut), scale=0.25, interpret=True))
    np.testing.assert_allclose(got[:real], want[:real], atol=1e-5)
    assert not got[real:].any()               # zeros: not NaN, not stale


def test_a_long_tail_is_scored_a_tile_of_queries_at_a_time(monkeypatch):
    """A bucket longer than ``PREFILL_SCORE_ROWS`` goes through the indexed
    prefill in tiles (``lax.map``): no ``[S, T]`` score array exists."""
    rng = np.random.default_rng(2)
    kp, vp, ip, tables = _pools(rng)
    monkeypatch.setattr(dsa, "PREFILL_SCORE_ROWS", 8)
    qs = jnp.asarray(rng.normal(size=(32, 4, 128)), jnp.float32)
    qis = jnp.asarray(rng.normal(size=(32, 2, 128)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(32, 2)), jnp.float32)
    a, b = (np.asarray(dsa.indexed_prefill(
        qs, qis, ws, kp, vp, ip, tables[0], 16, 45, topk=16, heads=2,
        scale=0.25, kernel=kern, interpret=True))
        for kern in ("reference", "pallas"))
    np.testing.assert_allclose(a[:29], b[:29], atol=1e-5)
    hlo = jax.jit(lambda *xs: dsa.indexed_prefill(
        *xs, tables[0], 16, 45, topk=16, heads=2, scale=0.25,
        kernel="pallas", interpret=True)).lower(qs, qis, ws, kp, vp, ip).as_text()
    assert "f32[32,64]" not in hlo.replace("tensor<32x64xf32>", "f32[32,64]")


# -- (c) through create_engine -------------------------------------------------

@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_engine_serves_across_topk_with_a_prefix_hit_and_counts(f32, tokens,
                                                                kernel):
    """The normal path: a cold prompt of the whole ``max_seq`` bucket, a
    request that grows past ``topk`` while it decodes, and one that hits the
    first's prefix; greedy tokens are the reference's argmax wherever its
    best two logits are apart, and ``stats()["sparse"]`` / the spans carry
    the selection."""
    model, tree, d = f32
    eng = inference.create_engine(model, num_slots=3, max_seq=128,
                                  min_bucket=16, block_size=BLOCK,
                                  kernel=kernel)
    eng.warmup(buckets=[16, 128])            # the two the three prompts take
    import time
    t0 = time.perf_counter()
    rng = np.random.default_rng(11)
    cold = rng.integers(0, 512, (100,), dtype=np.int32)   # the 128 bucket
    grow = tokens[:10]                                    # 10 -> 40 tokens
    hit = np.concatenate([cold[:64], tokens[:9]])
    handles = [eng.add_request(p, max_new_tokens=n)
               for p, n in ((cold, 12), (grow, 30))]
    eng.run()
    handles.append(eng.add_request(hit, max_new_tokens=8))
    eng.run()
    st = eng.stats()
    assert st["failures"]["failed"] == 0
    assert st["paging"]["prefix"]["hit_tokens"] == 64
    for h, prompt in zip(handles, (cold, grow, hit)):
        FAMILY.greedy_matches(tree, d, prompt, h.output_ids, margin=1e-3)
    sp = st["sparse"]
    assert sp["steps"] > 0 and 0 < sp["selected"] < sp["context"]
    assert sp["prefills"] == 2 and sp["prefill_context"] == 100 + 73
    steps = [r for r in _spans.snapshot(t0) if r[0] == "engine.step"
             and "dsa_context" in r[4]]
    assert steps and all(0 < r[4]["dsa_selected"] <= r[4]["dsa_context"]
                         for r in steps)
    assert any(r[4]["dsa_selected"] < r[4]["dsa_context"] for r in steps)
    assert any(r[4]["dsa_selected"] == r[4]["dsa_context"] for r in steps)
    fills = [r[4]["dsa_context"] for r in _spans.snapshot(t0)
             if r[0] == "engine.prefill" and "dsa_context" in r[4]]
    assert {0, 100, 73} <= set(fills)
    # the tail-prefill kernel's work items a layer, under the selection (a
    # prompt past ``topk``) or dense, and those whose chunk is one run of the
    # pool: on every prefill's span under ``kernel="pallas"`` and summed in
    # the stats; the reference path has no list
    work = [r[4] for r in _spans.snapshot(t0) if r[0] == "engine.prefill"
            and "prefill_items_full" in r[4]]
    names = ("prefill_items_full", "prefill_items_run")
    if kernel == "pallas":
        assert len(work) == len(fills) == 3
        for a in work:
            assert "prefill_items_window" not in a   # no layer keeps a window
            assert 0 <= a["prefill_items_run"] <= a["prefill_items_full"] >= 1
    else:
        assert not work
    assert [sp[k] for k in names] == [sum(a[k] for a in work) for k in names]
    assert eng.health()["kv_block_invariants"] == "ok"


def test_bf16_engine_serves_within_a_tolerance_the_wrong_selection_exceeds():
    """bf16 weights and pool through ``create_engine``: every greedy token's
    logit lies within a tolerance of the reference's best; a reference that
    selects the *first* ``topk`` tokens instead reads outside it."""
    model, tree, d = seeded("bfloat16")
    eng = inference.create_engine(model, num_slots=2, max_seq=128,
                                  min_bucket=16, block_size=BLOCK,
                                  kernel="pallas")
    eng.warmup(buckets=[128])
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, (70,), dtype=np.int32)
    h = eng.add_request(prompt, max_new_tokens=24)
    eng.run()
    sound, wrong = (FAMILY.served_gap(tree, d, prompt, h.output_ids, **kw)
                    for kw in ({}, dict(select=REF.select_first)))
    assert sound < 0.08 < wrong, (sound, wrong)  # read 0.030 and 0.270


# -- (d) the shares add up ------------------------------------------------------

def test_the_four_shares_are_the_whole_layer(f32):
    """One expert layer's output from the four shares of 4 experts each sums
    to the uncut reference's (all 16 experts), in the program and in the
    reference alike."""
    _model, tree, d = f32
    rng = np.random.default_rng(9)
    m = jnp.asarray(rng.normal(0, 1, (12, d["hidden"])), jnp.float32)
    lw = REF.layer_weights(tree, 1, d)
    full = dict(d, held=(0, 16))
    shapes = {k: v for k, v in REF.weight_shapes(
        tiny_config(num_experts=16, num_local_experts=16,
                    held_experts=[0, 16])).items() if k.startswith("layers.1.")}
    whole = weights.make(shapes, SEED + 1, jnp.float32)
    lw_all = {k: whole[f"layers.1.{k}"] for k in REF.LAYER_KEYS}
    want = np.asarray(REF.experts(m, lw_all, full, False))
    parts_ref, parts_prog = [], []
    for s in range(4):
        held = (4 * s, 4 * s + 4)
        lw_s = dict(lw_all, **{k: lw_all[k][held[0]:held[1]] for k in (
            "moe.w_gate", "moe.w_up", "moe.w_down")})
        parts_ref.append(np.asarray(REF.experts(m, lw_s, dict(d, held=held),
                                                False)))
        chosen, wts = km.route(m, lw_all["moe.router"], top_k=d["top_k"])
        y, _n, _t = km.held_experts_forward(
            m, chosen, wts, jnp.ones((12,), bool),
            jnp.concatenate([lw_s["moe.w_gate"], lw_s["moe.w_up"]], axis=2),
            lw_s["moe.w_down"], held=held, interpret=True)
        parts_prog.append(np.asarray(y))
    np.testing.assert_allclose(sum(parts_ref), want, atol=1e-5)
    np.testing.assert_allclose(sum(parts_prog), want, atol=1e-5)
    assert lw["moe.router"].shape == (d["hidden"], 16)

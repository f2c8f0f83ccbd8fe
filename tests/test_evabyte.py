"""The EvaByte-shaped decoder (EVA attention: the exact keys of the query's
own window and one learned summary a chunk of every window passed, over a
paged cache of two groups) at a tiny preset (hidden 64, 4 heads of 16,
windows of 32 positions in chunks of 4, 2 layers, 3 predictors, vocabulary
64), against the benchmark's plain reference
(``benchmarks/references/evabyte.py``: float32, a window's part at a time; it
imports nothing of the program)."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.obs import spans as _spans
from paddle_tpu.ops.pallas import eva_attention_kernel as eva
from paddle_tpu.serving.window_cache import (
    WindowedKVCache, WindowedPrefixCache)

from families import (  # noqa: F401 — the fixtures, and the common cases
    BLOCK, FAMILIES, compiled_steps, f32, family, pytest_generate_tests,
    tokens, want,
    test_admission_waits_for_blocks,
    test_bf16_engine_serves_within_a_tolerance,
    test_the_cache_refuses_what_it_has_no_form_for,
    test_the_model_states_its_cache_and_keeps_its_dtype)

FAMILY = FAMILIES["evabyte"]
REF = FAMILY.ref
engine, greedy_matches = FAMILY.engine, FAMILY.greedy_matches
W, C = 32, 4
ROWS = W // C


# -- (a) the full forward against the reference, every predictor --------------

def test_full_forward_equals_the_reference_over_three_windows(f32, tokens,
                                                              want):
    model, tree, d = f32
    got = np.asarray(model(paddle.to_tensor(tokens[None]))._value())[0]
    assert got.dtype == np.float32 and got.shape == (120, 64)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    every = np.asarray(model(paddle.to_tensor(tokens[None]),
                             all_heads=True)._value())[0]
    h = REF.hidden(tree, jnp.asarray(tokens), d)
    ref_all = np.asarray(REF.logits_all(
        {k: tree[k] for k in REF.HEAD_KEYS}, h, d))
    assert every.shape == ref_all.shape == (120, 3, 64)
    np.testing.assert_allclose(every, ref_all, atol=1e-4, rtol=0)
    np.testing.assert_array_equal(every[:, 0], got)
    # the summaries are read: a reference that pools by the plain mean differs
    other = np.asarray(REF.logits_rows(
        {k: tree[k] for k in REF.HEAD_KEYS},
        REF.hidden(tree, jnp.asarray(tokens), d,
                   summarise=REF.summaries_uniform), d))
    assert np.abs(other[:W] - want[:W]).max() < 1e-6    # window 0: no summary
    assert np.abs(other - want).max() > 1e-4


def test_every_layer_has_its_summary_parameters():
    assert len(FAMILY.tiny_model().summary_params()) == 2


# -- (b) prefill in pieces, decode across two boundaries: logits --------------

@pytest.mark.parametrize("kernel", ["reference", "pallas"])
def test_prefill_in_pieces_then_decode_across_two_window_ends(f32, tokens,
                                                              want, kernel):
    """A 40-token prompt in two pieces (a cold 24-token bucket, then a
    16-token tail behind its three blocks that crosses the end of window 0
    and publishes it inside its own program), then teacher-forced decode
    from 40 to 100 tokens across the ends of windows 1 and 2, each followed
    by the publishing call; another slot decodes inside its first window
    beside it.  Every logit row the engine would sample from equals the
    reference's one full forward."""
    model, _tree, d = f32
    cache = WindowedKVCache(
        num_slots=3, num_layers=d["layers"], max_seq=128,
        sides=model.cache_spec().sides, block_size=BLOCK, kernel=kernel,
        window=W, chunk=C, num_blocks=40, num_summary_blocks=9)
    assert [tuple(b.shape) for b in cache.buffers()] == \
        [(40, BLOCK, 4, 128)] * 2 * d["layers"]
    assert [tuple(b.shape) for b in cache.summary_buffers()] == \
        [(9, ROWS, 4, 128)] * 2 * d["layers"]

    prefill_step, decode_step = compiled_steps(model, cache,
                                               counts="row_counts")

    def prefill(slot, ids, start, length):
        row = prefill_step(slot, ids, start, length)
        cache.release_windows(slot, length)
        return row

    def decode(pos_of):
        active = np.zeros(3, np.int32)
        step = np.zeros((3, 1), np.int32)
        for s, pos in pos_of.items():
            if cache.windows_pending(s, pos):            # the engine's order
                for i, (phi, mu) in enumerate(model.summary_params()):
                    cache.publish(i, s, cache.published(s), True,
                                  phi._value(), mu._value())
                assert cache.release_windows(s, pos) == W // BLOCK
            assert cache.ensure_capacity(s, pos)
            active[s], step[s, 0] = 1, tokens[pos]
        out, counts = decode_step(step, active)
        for s, pos in pos_of.items():
            np.testing.assert_allclose(out[s, 0], want[pos], atol=1e-4,
                                       rtol=0)
        return counts

    assert cache.begin_sequence(0, None, 0, 24, total=128)
    assert len(cache._slot_windows[0]) == 4         # a life's summary blocks
    np.testing.assert_allclose(prefill(0, tokens[:24], 0, 24), want[23],
                               atol=1e-4, rtol=0)
    shared = ([], list(cache.owned_blocks(0)[1]))
    assert cache.begin_sequence(2, shared, 24, 16, total=128)
    before = len([b for b in cache.owned_blocks(2)[1] if b])
    np.testing.assert_allclose(prefill(2, tokens[24:40], 24, 40), want[39],
                               atol=1e-4, rtol=0)
    held = [b for b in cache.owned_blocks(2)[1] if b]
    assert before - len(held) == W // BLOCK and cache.published(2) == 1
    # slot 2 from 40 to 100 (windows 1 and 2 end on the way); slot 0 beside
    # it inside window 0 for the first steps: the step is dense while every
    # running slot is in its first window, never here
    for pos in range(40, 100):
        pos_of = {2: pos}
        if pos < 44:
            pos_of[0] = 24 + pos - 40
        counts = decode(pos_of)
        exact = sum(p % W + 1 for p in pos_of.values())
        summary = sum(p // W * ROWS for p in pos_of.values())
        context = sum(p + 1 for p in pos_of.values())
        assert counts == [(exact, summary, context)] * d["layers"]
    assert cache.published(2) == 3
    assert cache.exact_blocks_released == 3 * (W // BLOCK)
    assert cache.check_invariants() == [] and cache.allocator.check() == []


# -- (c) a context of one window or less: no summary item, the dense result ----

def test_a_context_inside_one_window_equals_the_dense_causal_path(f32):
    """A slot inside its first window has no summary item: the decode step is
    the windowed kernel's one call a layer (no fork beside it: no cell of the
    benchmark ran the dense side, and the fork cost every step), and a tail's
    prefill the windowed prefill kernel's; both equal what the K/V pool's
    dense kernels give on the same blocks."""
    from paddle_tpu.serving.paging import PagedKVCache

    model, _tree, d = f32
    kw = dict(num_slots=2, num_layers=d["layers"], max_seq=64,
              sides=model.cache_spec().sides, block_size=BLOCK,
              kernel="pallas", num_blocks=20)
    win = WindowedKVCache(window=W, chunk=C, num_summary_blocks=3, **kw)
    rng = np.random.default_rng(2)
    shape = (2, 1, 4, 16)
    q, k, v = (paddle.to_tensor(rng.normal(size=shape).astype(np.float32))
               for _ in range(3))
    dense = PagedKVCache(**kw)
    tail = tuple(paddle.to_tensor(rng.normal(size=(1, 16, 4, 16)).astype(
        np.float32)) for _ in range(3))
    zero = paddle.to_tensor(np.int32(0))
    phi = mu = jnp.zeros((4, 16), jnp.float32)
    for cache in (win, dense):
        assert cache.begin_sequence(0, None if cache is win else [], 0, 16)
    # the prefill: the windowed call on one window against the dense read
    out = win.windowed_prefill_attention(0, zero, *tail, phi, mu, zero,
                                         paddle.to_tensor(np.int32(16)))
    dense.prefill_write(0, zero, tail[1], tail[2], zero)
    ref = dense.dense_prefill_attention(0, zero, tail[0], zero)
    np.testing.assert_allclose(np.asarray(out._value()),
                               np.asarray(ref._value()), atol=1e-5)
    # a decode step behind those 9 of the 16 tokens
    for cache in (win, dense):
        cache.set_length(0, 9)
    act = paddle.to_tensor(np.asarray([1, 0], np.int32))
    got, exact, summary, context = win.windowed_decode_attention(0, q, k, v,
                                                                 act)
    assert (int(exact), int(summary), int(context)) == (10, 0, 10)
    plain = dense.decode_attention(0, q, k, v, act)
    np.testing.assert_allclose(np.asarray(got._value())[0],
                               np.asarray(plain._value())[0], atol=1e-5)
    assert win.decode_items_fn()(9) == 1 == dense.decode_items_fn()(9)


# -- (d) a prefix hit of both kinds --------------------------------------------

def test_a_prefix_hit_of_both_kinds_gives_a_cold_runs_tokens(f32, tokens):
    """A document of 88 tokens (two windows and three blocks) served once;
    a question behind it hits 64 tokens by summary blocks and 24 by exact
    blocks and decodes what the cold run decoded; once the document's partial
    window is gone from the cache, a hit into it is cut back to the window's
    start."""
    model, tree, d = f32
    doc, question = tokens[:88], tokens[88:100]
    prompt = np.concatenate([doc, question])
    eng = engine(model)
    c = eng.add_request(prompt, max_new_tokens=20)           # cold
    eng.run()
    pc = eng.prefix_cache
    assert isinstance(pc, WindowedPrefixCache)
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == 0
    pc.clear()
    first = eng.add_request(doc, max_new_tokens=1)
    eng.run()
    assert len(pc.windows) == 2 and len(pc.exact) == 3 and first.finished
    assert eng.prefix_probe(prompt) == 88
    released = eng.stats()["eva"]["exact_blocks_released"]
    h = eng.add_request(prompt, max_new_tokens=20)
    eng.run()
    st = eng.stats()
    assert st["paging"]["prefix"]["hit_tokens"] == 88
    assert h.output_ids == c.output_ids
    greedy_matches(tree, d, prompt, h.output_ids)
    # h closed window 2 (at 96) inside its 16-token tail: 88 + 12 = 100
    spans = [r[4] for r in _spans.snapshot() if r[0] == "engine.prefill"]
    assert (spans[-1]["bucket"], spans[-1]["eva_windows_published"]) == (16, 1)
    assert st["eva"]["exact_blocks_released"] - released >= W // BLOCK
    # the document's partial window gone from the cache: another question
    # behind it hits the two windows and no further
    pc.exact.clear()
    other = np.concatenate([doc, tokens[100:112]])
    assert eng.prefix_probe(other) == 64
    again = eng.add_request(other, max_new_tokens=8)
    eng.run()
    greedy_matches(tree, d, other, again.output_ids)
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] == 88 + 64
    assert eng.health()["kv_block_invariants"] == "ok"


# -- (e) roll-over, the allocators' audit, preempt / resume ---------------------

def test_a_roll_over_frees_a_windows_blocks_and_resume_reproduces(f32,
                                                                  tokens):
    """A slot that decodes across a window's end holds a window's blocks
    fewer after the step; both allocators' audits stay clean; a low-priority
    request preempted after it has passed a boundary resumes by a hit of
    whole windows and reproduces its tokens."""
    model, tree, d = f32
    eng = engine(model, buckets=(32,), num_slots=1, max_preemptions=2)
    prompt = tokens[:60]
    low = eng.add_request(prompt, max_new_tokens=30, priority=0)
    held = []
    while len(low.output_ids) < 12:                 # past 64: window 1 ended
        eng.step()
        held.append(len([b for b in eng.cache.owned_blocks(0)[1] if b]))
    drops = [a - b for a, b in zip(held, held[1:]) if a > b]
    assert drops == [W // BLOCK - 1]    # 4 blocks go, the next one is taken
    assert eng.cache.published(0) == 2
    assert eng.cache.check_invariants() == []
    assert eng.cache.allocator.check() == []
    assert eng.cache.summary_allocator.check() == []
    so_far = list(low.output_ids)
    high = eng.add_request(tokens[100:110], max_new_tokens=4, priority=5)
    eng.run()
    assert low.preemptions == 1 and high.finished and low.finished
    assert low.output_ids[:len(so_far)] == so_far and len(low.output_ids) == 30
    greedy_matches(tree, d, prompt, low.output_ids)
    st = eng.stats()
    assert st["paging"]["prefix"]["hit_tokens"] >= 32     # resumed by a hit
    assert st["eva"]["windows_published_decode"] >= 2
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_cold_long_prompt_goes_in_a_window_at_a_time(f32, tokens):
    """A cold prompt of 120 tokens against a pool of 13 blocks, fewer than
    its 128-token bucket's 16: it is prefilled in four programs of a window
    or less, each closing its window and letting its blocks go before the
    next takes its own, so it is admitted at once, holds two windows of
    blocks at most, fails nothing and blocks no one behind it; a sampled
    request served so draws what the same request draws behind a hit.  A
    pool that cannot hold two windows rejects it at the door."""
    import time

    from paddle_tpu.serving import SamplingParams

    model, tree, d = f32
    eng = engine(model, buckets=(16, 32), num_slots=2, num_kv_blocks=14)
    peak = []
    sound = eng.cache.extend_tail

    def watched(slot, start, bucket, **kw):
        ok = sound(slot, start, bucket, **kw)
        peak.append(eng.cache.allocator.stats()["used"])
        return ok

    eng.cache.extend_tail = watched
    long, short = tokens[:120], tokens[40:50]
    t0 = time.perf_counter()
    a = eng.add_request(long, max_new_tokens=6)
    b = eng.add_request(short, max_new_tokens=6)
    eng.step()
    assert len(eng.running) == 2 and not eng.queue      # both admitted
    eng.run()
    assert a.finished and b.finished and not a.error and not b.error
    assert eng.stats()["failures"]["failed"] == 0
    fills = [r[4]["bucket"] for r in _spans.snapshot(t0)
             if r[0] == "engine.prefill"]
    assert fills == [32, 32, 32, 32, 16]                # a's four, then b's
    assert len(peak) == 3 and max(peak) <= 2 * (W // BLOCK)
    greedy_matches(tree, d, long, a.output_ids)
    greedy_matches(tree, d, short, b.output_ids)
    assert eng.stats()["eva"]["windows_published_prefill"] == 3
    # sampled: in pieces (cold), then behind a hit of three windows
    sp = SamplingParams(temperature=0.9, top_k=8, top_p=0.95, seed=11)
    eng.prefix_cache.clear()
    cold = eng.add_request(long, max_new_tokens=6, sampling=sp)
    eng.run()
    hit = eng.add_request(long, max_new_tokens=6, sampling=sp)
    eng.run()
    assert eng.stats()["paging"]["prefix"]["hit_tokens"] >= 96
    assert cold.output_ids == hit.output_ids and len(hit.output_ids) == 6
    assert eng.health()["kv_block_invariants"] == "ok"
    small = engine(model, buckets=(32,), num_slots=1, num_kv_blocks=8)
    with pytest.raises(ValueError, match="needs 8 KV blocks"):
        small.add_request(long, max_new_tokens=2)
    assert small.buckets == [8, 16, 32]         # none above the window


# -- (f) the kernels against their oracles --------------------------------------

def _pools(rng, dtype=jnp.float32):
    N, NS, H, D = 40, 10, 4, 128
    mk = lambda *s: jnp.asarray(rng.normal(size=s), dtype)      # noqa: E731
    return (mk(N, BLOCK, H, D), mk(N, BLOCK, H, D), mk(NS, ROWS, H, D),
            mk(NS, ROWS, H, D),
            jnp.asarray(rng.integers(1, N, (3, 16)), jnp.int32),
            jnp.asarray(rng.integers(1, NS, (3, 4)), jnp.int32))


@pytest.mark.parametrize("kernel_dtype", ["float32", "bfloat16"])
def test_decode_and_prefill_kernels_equal_their_oracles(kernel_dtype):
    rng = np.random.default_rng(1)
    dt = jnp.dtype(kernel_dtype)
    *pools, tables, stables = _pools(rng, dt)
    tol = 2e-5 if kernel_dtype == "float32" else 3e-2
    kw = dict(window=W, scale=128 ** -0.5)
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), dt)
    lengths = jnp.asarray([5, 70, 127], jnp.int32)
    for active in ([1, 1, 1], [0, 1, 0]):
        active = jnp.asarray(active, jnp.int32)
        got = eva.eva_paged_decode(q, *pools, tables, stables, lengths,
                                   active, interpret=True, **kw)
        want = eva.eva_decode_reference(q, *pools, tables, stables, lengths,
                                        active, **kw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
    # a tail inside one window, one across a window's end, one of several
    # windows, the first window alone, and the last
    for S, start in ((16, 8), (16, 24), (64, 40), (8, 0), (32, 96)):
        qp = jnp.asarray(rng.normal(size=(S, 4, 128)), dt)
        args = (qp, *pools, tables[1], stables[1], jnp.int32(start))
        got = eva.eva_paged_prefill(*args, interpret=True, **kw)
        want = eva.eva_prefill_reference(*args, **kw)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)


def test_chunk_summaries_are_the_references():
    rng = np.random.default_rng(3)
    k, v = (jnp.asarray(rng.normal(size=(64, 4, 16)), jnp.float32)
            for _ in range(2))
    phi, mu = (jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
               for _ in range(2))
    got = eva.chunk_summaries(k, v, phi, mu, chunk=C, scale=16 ** -0.5)
    want = REF.summaries(k, v, phi, mu, C)
    for g, w in zip(got, want):
        assert g.shape == (16, 4, 16)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6)
    assert eva.decode_items(np.int32(70), window=W, chunk_tokens=16) == 2 + 1
    assert eva.decode_items(np.int32(31), window=W, chunk_tokens=16) == 2


# -- the engine: spans, counters, refusals --------------------------------------

def test_the_engine_counts_rows_and_windows_on_its_spans(f32, tokens):
    import time

    model, tree, d = f32
    eng = engine(model, buckets=(8, 32))
    t0 = time.perf_counter()
    h = eng.add_request(tokens[:70], max_new_tokens=30)
    eng.run()
    greedy_matches(tree, d, tokens[:70], h.output_ids)
    rows = _spans.snapshot(t0)
    steps = [r[4] for r in rows if r[0] == "engine.step"
             and "eva_context" in r[4]]
    assert len(steps) == 29
    for i, a in enumerate(steps):
        pos = 70 + i
        assert (a["eva_exact_rows"], a["eva_summary_rows"],
                a["eva_context"]) == (pos % W + 1, pos // W * ROWS, pos + 1)
    assert sum(a["eva_windows_published"] for a in steps) == 1
    fills = [r[4] for r in rows if r[0] == "engine.prefill"
             and "eva_windows" in r[4]]
    # the cold prompt went in a window at a time: three programs
    assert [(a["bucket"], a["eva_windows"], a["eva_windows_published"])
            for a in fills] == [(32, 1, 1), (32, 1, 1), (8, 1, 0)]
    i = np.arange(70)
    assert sum(a["eva_rows"] for a in fills) == \
        int(np.sum(i % W + 1 + i // W * ROWS))
    assert [a["eva_keys"] for a in fills] == [W, W + ROWS, 6 + 2 * ROWS]
    pubs = [r[4] for r in rows if r[0] == "engine.publish_window"]
    assert [(a["window"], a["exact_blocks_released"]) for a in pubs] == \
        [(2, W // BLOCK)]
    ev = eng.stats()["eva"]
    assert (ev["steps"], ev["windows_published_decode"],
            ev["windows_published_prefill"], ev["prefill_windows"]) == \
        (29, 1, 2, 3)
    assert ev["exact_rows"] == sum(a["eva_exact_rows"] for a in steps)
    assert ev["exact_blocks_released"] == 3 * (W // BLOCK)
    assert ev["summary_blocks_in_use"] == 2 and ev["exact_blocks_in_use"] == 0
    assert eng.stats()["compile_cache"]["misses"] == 2 + 2
    #                            two warmed buckets, decode, publish: no more


def test_the_summary_groups_size_is_refused_where_it_means_nothing():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    with pytest.raises(ValueError, match="num_summary_blocks"):
        inference.create_engine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                                num_summary_blocks=4)

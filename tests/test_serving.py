"""Serving engine: KV-cache decode correctness, continuous-batching
scheduler, zero-recompile steady state, metrics.

Correctness tests run the cache paths EAGERLY (no XLA compile) so they cost
milliseconds; the engine tests compile the real bucketed prefill + decode
programs once and then assert the executable cache's miss counter stays
flat through admit/retire churn (the ISSUE 3 acceptance criterion).
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fault_tolerance import (
    InjectedFault, ServingFaultPlan,
)
from paddle_tpu.models import (
    GPTForCausalLM, LlamaForCausalLM, gpt_tiny, llama_tiny,
)
from paddle_tpu.serving import (
    CacheContext, Engine, EngineStopped, KVCache, QueueFull,
    SamplingParams, sample,
)


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


def _full_logits(model, seq):
    """Full-recompute (no cache) logits for every position, [S, V]."""
    with paddle.no_grad():
        out = model(paddle.to_tensor(np.asarray(seq, np.int64)[None]))
    return out.numpy()[0]


def _ref_greedy(model, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(np.argmax(_full_logits(model, seq)[-1])))
    return seq[len(prompt):]


def _assert_greedy_chain(model, prompt, out_ids):
    """Assert ``out_ids`` IS the no-cache greedy generation for ``prompt``
    using ONE full-recompute forward: causal attention makes the logits at
    position i of the whole sequence identical to the logits a step-by-step
    no-cache loop computes, so token-by-token argmax equality here is exact
    reference parity (by induction over the chain)."""
    L = len(prompt)
    full = list(prompt) + [int(t) for t in out_ids]
    logits = _full_logits(model, full[:-1])         # [L+n-1, V]
    for i, t in enumerate(out_ids):
        assert int(np.argmax(logits[L - 1 + i])) == int(t), (i, t)


def _cached_generate_logits(model, cfg, kv_heads, prompt, steps, *,
                            slot=1, num_slots=3, max_seq=32, bucket=16):
    """Greedy-generate through the cache paths eagerly, returning the
    logits emitted at every step (prefill last-token + each decode)."""
    cache = KVCache(num_slots=num_slots, num_layers=cfg.num_hidden_layers,
                    max_seq=max_seq, num_kv_heads=kv_heads,
                    head_dim=cfg.head_dim)
    L = len(prompt)
    ids = np.zeros((1, bucket), np.int64)
    ids[0, :L] = prompt
    collected = []
    with paddle.no_grad():
        ctx = CacheContext(cache, "prefill",
                           slot=paddle.to_tensor(np.int32(slot)),
                           length=paddle.to_tensor(np.int32(L)))
        logits = model(paddle.to_tensor(ids), cache_ctx=ctx)
        cache.set_length(slot, L)
        collected.append(logits.numpy()[0, L - 1])
        seq = list(prompt) + [int(np.argmax(collected[-1]))]
        active = np.zeros((num_slots,), np.int32)
        active[slot] = 1
        for _ in range(steps):
            toks = np.zeros((num_slots, 1), np.int64)
            toks[slot, 0] = seq[-1]
            dctx = CacheContext(cache, "decode",
                                active=paddle.to_tensor(active))
            lg = model(paddle.to_tensor(toks), cache_ctx=dctx)
            cache.advance(paddle.to_tensor(active))
            collected.append(lg.numpy()[slot, 0])
            seq.append(int(np.argmax(collected[-1])))
    return collected, seq[L:]


class TestDecodeCorrectness:
    """Cached greedy decode must match full-recompute logits (ISSUE 3
    satellite: fp-tolerance parity for tiny GPT and tiny GQA Llama)."""

    def _check(self, model, cfg, kv_heads):
        rs = np.random.RandomState(0)
        prompt = rs.randint(0, cfg.vocab_size, (7,)).tolist()
        L, steps = len(prompt), 5
        got, got_ids = _cached_generate_logits(
            model, cfg, kv_heads, prompt, steps)
        # one no-cache forward over the whole generated sequence yields the
        # step-by-step reference logits for every emitted position (causal)
        ref_all = _full_logits(model, (prompt + got_ids)[:-1])
        for i, step_logits in enumerate(got):
            ref = ref_all[L - 1 + i]
            np.testing.assert_allclose(step_logits, ref,
                                       atol=2e-4, rtol=2e-4)
            assert int(np.argmax(step_logits)) == int(np.argmax(ref))
        _assert_greedy_chain(model, prompt, got_ids)

    def test_gpt_cache_matches_full_recompute(self, gpt):
        self._check(gpt, gpt.config, gpt.config.num_attention_heads)

    def test_llama_gqa_cache_matches_full_recompute(self, llama):
        assert llama.config.n_kv_heads < llama.config.num_attention_heads
        self._check(llama, llama.config, llama.config.n_kv_heads)

    def test_slot_reuse_after_retire(self, gpt):
        """A retired slot's stale cache bytes must never leak into the next
        request served from the same slot."""
        cfg = gpt.config
        rs = np.random.RandomState(1)
        long_p = rs.randint(0, cfg.vocab_size, (12,)).tolist()
        short_p = rs.randint(0, cfg.vocab_size, (4,)).tolist()
        cache = KVCache(num_slots=2, num_layers=cfg.num_hidden_layers,
                        max_seq=32, num_kv_heads=cfg.num_attention_heads,
                        head_dim=cfg.head_dim)
        for prompt in (long_p, short_p):   # same slot, longer first
            L = len(prompt)
            ids = np.zeros((1, 16), np.int64)
            ids[0, :L] = prompt
            with paddle.no_grad():
                ctx = CacheContext(cache, "prefill",
                                   slot=paddle.to_tensor(np.int32(1)),
                                   length=paddle.to_tensor(np.int32(L)))
                out = gpt(paddle.to_tensor(ids), cache_ctx=ctx)
                cache.set_length(1, L)
                seq = list(prompt) + [int(np.argmax(out.numpy()[0, L - 1]))]
                active = paddle.to_tensor(np.asarray([0, 1], np.int32))
                for _ in range(3):
                    toks = np.zeros((2, 1), np.int64)
                    toks[1, 0] = seq[-1]
                    dctx = CacheContext(cache, "decode", active=active)
                    lg = gpt(paddle.to_tensor(toks), cache_ctx=dctx)
                    cache.advance(active)
                    seq.append(int(np.argmax(lg.numpy()[1, 0])))
            _assert_greedy_chain(gpt, prompt, seq[L:])

    def test_cache_validation_and_capacity(self, gpt):
        cfg = gpt.config
        cache = KVCache(num_slots=2, num_layers=2, max_seq=8,
                        num_kv_heads=4, head_dim=16)
        assert cache.nbytes() == 2 * 2 * 2 * 8 * 4 * 16 * 4
        with pytest.raises(ValueError):
            KVCache(num_slots=0, num_layers=1, max_seq=8,
                    num_kv_heads=1, head_dim=4)
        with pytest.raises(ValueError):
            CacheContext(cache, "bogus")


class TestSampling:
    def test_greedy(self):
        assert sample(np.asarray([0.1, 3.0, -1.0]), SamplingParams()) == 1

    def test_temperature_seeded_deterministic(self):
        p = SamplingParams(temperature=0.8, seed=123)
        logits = np.random.RandomState(0).randn(64)
        a = sample(logits, p, np.random.RandomState(123))
        b = sample(logits, p, np.random.RandomState(123))
        assert a == b

    def test_top_k_restricts_support(self):
        logits = np.asarray([10.0, 9.0, -50.0, -50.0])
        p = SamplingParams(temperature=1.0, top_k=2)
        rng = np.random.RandomState(0)
        assert all(sample(logits, p, rng) in (0, 1) for _ in range(20))

    def test_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(temperature=-1.0)


class TestEngineChurn:
    """ISSUE 3 acceptance: under admit/retire churn of mixed prompt
    lengths, zero compile-cache misses after warmup AND cached greedy
    output identical to the no-cache reference generation."""

    def test_gpt_zero_recompile_churn_and_greedy_parity(self, gpt):
        eng = Engine(gpt, num_slots=3, max_seq=32, min_bucket=8,
                     block_size=8)
        assert eng.buckets == [8, 16, 32]
        eng.warmup()
        warm_misses = eng.metrics.compile_misses
        assert warm_misses == len(eng.buckets) + 1      # prefills + decode

        rs = np.random.RandomState(1)
        lengths = [3, 10, 17, 5, 12, 20, 7, 25]        # hits every bucket
        prompts = [rs.randint(0, 128, (L,)).tolist() for L in lengths]
        streamed = []
        # a generous deadline exercises the hardened deadline-checking
        # path on every step without ever expiring
        reqs = [eng.add_request(p, max_new_tokens=5, deadline_s=60.0,
                                stream_cb=lambda t, r: streamed.append(
                                    (r.request_id, t)))
                for p in prompts]
        eng.run()

        st = eng.stats()
        # zero-recompile steady state, measured by the executable cache
        assert eng.metrics.compile_misses == warm_misses, st["compile_cache"]
        assert st["compile_cache"]["hits"] > 0
        # greedy parity with full-recompute generation, every request
        for p, r in zip(prompts, reqs):
            assert r.finished and len(r.output_ids) == 5
            _assert_greedy_chain(gpt, p, r.output_ids)
        # streaming delivered every token in order
        for r in reqs:
            got = [t for rid, t in streamed if rid == r.request_id]
            assert got == r.output_ids
        # metrics sanity + JSON-serializable /stats payload
        assert st["requests"]["completed"] == len(prompts)
        assert st["requests"]["running"] == 0 and st["queue_depth"] == 0
        assert st["tokens"]["decode"] == len(prompts) * 4  # 1st via prefill
        assert st["ttft_ms"]["count"] == len(prompts)
        assert st["inter_token_ms"]["count"] > 0
        assert 0 < st["slot_occupancy"] <= 1
        assert st["prefills_by_bucket"] == {8: 3, 16: 2, 32: 3}
        assert all(r.ttft_s is not None and r.ttft_s >= 0 for r in reqs)
        # the hardened lifecycle never fired on the happy path: every
        # failure counter is zero, no slot leaked, engine stays healthy
        fl = st["failures"]
        assert fl["failed"] == 0 and fl["cancelled"] == 0
        assert fl["rejected"] == 0 and fl["deadline_expired"] == 0
        assert fl["step_failures"] == 0 and fl["step_retries"] == 0
        assert fl["callback_errors"] == 0
        assert sorted(eng.free_slots) == [0, 1, 2]
        assert st["health"]["state"] == "active"
        assert st["health"]["consecutive_step_failures"] == 0
        json.dumps(st)
        # exported through the profiler surface too
        import paddle_tpu.profiler as profiler

        assert st["name"] in profiler.serving_stats()

    @pytest.mark.slow  # demoted ISSUE 20: the GQA engine path is held
    # in tier-1 by TIER1_CRITICAL siblings (paged_kernel + spec_decode
    # GQA greedy-bitwise, sharded_serving GQA parity pairs) and the
    # churn/zero-recompile law by test_gpt_zero_recompile_churn above —
    # this pays a second full Llama warmup for no unique assertion
    def test_llama_gqa_engine_zero_recompile(self, llama):
        eng = Engine(llama, num_slots=2, max_seq=16, min_bucket=16)
        assert eng.buckets == [16]
        rs = np.random.RandomState(2)
        first = [rs.randint(0, 128, (L,)).tolist() for L in (4, 9)]
        outs = eng.generate(first, max_new_tokens=3)    # cold: compiles here
        misses = eng.metrics.compile_misses
        assert misses == 2                              # 1 bucket + decode
        second = [rs.randint(0, 128, (L,)).tolist() for L in (11, 2, 7)]
        outs2 = eng.generate(second, max_new_tokens=3)
        assert eng.metrics.compile_misses == misses     # steady state
        for p, o in zip(first + second, outs + outs2):
            _assert_greedy_chain(llama, p, o)

    def test_engine_request_validation(self, gpt):
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16)
        with pytest.raises(ValueError):
            eng.add_request([])
        with pytest.raises(ValueError):
            eng.add_request(list(range(17)))
        with pytest.raises(ValueError):
            Engine(gpt, max_seq=10_000)                 # > max_position
        with pytest.raises(ValueError):
            Engine(gpt, max_seq=16, min_bucket=0)
        with pytest.raises(ValueError):
            eng.add_request([1, 2], max_new_tokens=0)
        eng.add_request([1, 2, 3], max_new_tokens=1)
        with pytest.raises(RuntimeError):
            eng.warmup()                                # traffic enqueued

    def test_from_config_entries(self):
        from paddle_tpu import inference
        from paddle_tpu.models import GPTConfig

        eng = inference.create_engine("gpt:tiny", num_slots=2, max_seq=16)
        assert isinstance(eng, Engine)
        assert isinstance(Engine.from_config(gpt_tiny(), max_seq=16), Engine)
        with pytest.raises(KeyError):
            Engine.from_config("gpt:nope")
        with pytest.raises(TypeError):
            Engine.from_config(12345)
        assert GPTConfig  # silence linter


class TestEngineStops:
    def test_eos_and_capacity_stop(self, gpt):
        eng = Engine(gpt, num_slots=2, max_seq=16, min_bucket=16)
        # use a token the greedy reference actually emits as the eos
        ref = _ref_greedy(gpt, [5, 6, 7], 4)
        eos = ref[1]
        expect = ref[:ref.index(eos) + 1]
        r = eng.add_request([5, 6, 7], max_new_tokens=8, eos_token_id=eos)
        eng.run()
        assert r.output_ids == expect                   # stopped at eos
        # capacity: prompt 14 in a 16-deep cache → decode can write at
        # positions 14 and 15 only, so exactly 3 tokens are emitted (the
        # last one needs no cache line of its own)
        r2 = eng.add_request(list(range(14)), max_new_tokens=8)
        eng.run()
        assert r2.finished and len(r2.output_ids) == 3
        # temperature sampling stays in-vocab and is reproducible by seed
        sp = SamplingParams(temperature=1.0, seed=7)
        r3 = eng.add_request([9, 8], max_new_tokens=4, sampling=sp)
        eng.run()
        r4 = eng.add_request([9, 8], max_new_tokens=4,
                             sampling=SamplingParams(temperature=1.0,
                                                     seed=7))
        eng.run()
        assert r3.output_ids == r4.output_ids
        assert all(0 <= t < 128 for t in r3.output_ids)


class TestResilience:
    """ISSUE 4: serving-side resilience — request lifecycle hardening,
    backpressure, error isolation, watchdog, and engine drain.  All on
    eager tiny models with one prefill bucket so the added wall-time
    stays small; engines are reused across tests (metrics asserted as
    deltas) to bound compile count."""

    def test_fault_plan_env_parsing(self):
        env = {"PADDLE_TPU_FT_SERVING_FAULTS":
               "serving.prefill@1x2, serving.decode@3:stall=0.01"}
        plan = ServingFaultPlan.from_env(env)
        assert plan.armed
        for n in (1, 2):
            with pytest.raises(InjectedFault, match=f"call #{n}"):
                plan.check("serving.prefill")
        plan.check("serving.prefill")               # window passed
        plan.check("serving.decode")
        plan.check("serving.decode")
        t0 = time.perf_counter()
        plan.check("serving.decode")                # stalls, not raises
        assert time.perf_counter() - t0 >= 0.01
        assert plan.calls("serving.decode") == 3
        assert not ServingFaultPlan.from_env({}).armed
        with pytest.raises(ValueError):
            ServingFaultPlan.from_env(
                {"PADDLE_TPU_FT_SERVING_FAULTS": "serving.decode"})
        with pytest.raises(ValueError):
            ServingFaultPlan.from_env(
                {"PADDLE_TPU_FT_SERVING_FAULTS": "serving.nope@1"})
        with pytest.raises(ValueError):
            ServingFaultPlan.from_env(
                {"PADDLE_TPU_FT_SERVING_FAULTS": "serving.decode@1:die=1"})

    @pytest.fixture(scope="class")
    def rengine(self, gpt):
        """Shared resilience engine: one bucket, two slots (reused across
        tests — metrics are asserted as deltas)."""
        return Engine(gpt, num_slots=2, max_seq=16, min_bucket=16)

    def test_enqueue_rejection_and_backpressure(self, gpt, rengine):
        eng = rengine
        base = eng.metrics.requests_rejected
        eng.max_queue, eng.queue_policy = 1, "reject"
        try:
            # malformed requests are rejected at enqueue, never admitted
            with pytest.raises(ValueError) as ei:
                eng.add_request([])
            assert ei.value.request.state == "rejected"
            assert ei.value.request.error == "empty prompt"
            with pytest.raises(ValueError):
                eng.add_request([1, 2], deadline_s=-1.0)
            assert eng.metrics.requests_rejected - base == 2
            # reject policy: a full queue raises QueueFull with the depth
            r0 = eng.add_request([5, 6], max_new_tokens=2)
            with pytest.raises(QueueFull) as qi:
                eng.add_request([7, 8])
            assert qi.value.depth == 1
            assert qi.value.request.state == "rejected"
            # block policy with a zero budget degrades to reject
            eng.queue_policy = "block"
            with pytest.raises(QueueFull):
                eng.add_request([7, 8], block_timeout_s=0.0)
            assert eng.metrics.requests_rejected - base == 4
            # block policy with budget: drives step() until space frees
            rz = eng.add_request([9, 10], max_new_tokens=2,
                                 block_timeout_s=30.0)
            assert r0.state in ("running", "finished")  # blocking admitted
            eng.run()
            assert r0.finished and len(r0.output_ids) == 2
            assert rz.finished and len(rz.output_ids) == 2
            assert eng.metrics.requests_rejected - base == 4
        finally:
            eng.max_queue, eng.queue_policy = None, "reject"

    def test_cancel_queued_running_and_from_cb(self, gpt, rengine):
        eng = rengine
        base = eng.metrics.requests_cancelled
        # queued: cancel() is honored immediately, before any admission
        r1 = eng.add_request([1, 2, 3], max_new_tokens=4)
        assert r1.cancel() is True
        assert r1.state == "cancelled" and len(eng.queue) == 0
        assert r1.cancel() is False                 # already terminal
        # running: retired at the next step boundary, slot reclaimed
        r2 = eng.add_request([4, 5], max_new_tokens=8)
        eng.step()                                  # admit + one decode
        assert r2.state == "running"
        emitted = len(r2.output_ids)
        assert r2.cancel() is True
        eng.run()
        assert r2.state == "cancelled"
        assert len(r2.output_ids) == emitted        # no tokens after cancel
        # a request may cancel itself from its own stream callback
        r3 = eng.add_request(
            [6, 7], max_new_tokens=10,
            stream_cb=lambda t, r: r.cancel() if len(r.output_ids) >= 2
            else None)
        eng.run()
        assert r3.state == "cancelled" and len(r3.output_ids) == 2
        assert eng.metrics.requests_cancelled - base == 3
        assert sorted(eng.free_slots) == [0, 1]
        assert r2.error is None                     # cancelled, not failed

    def test_deadline_expiry(self, gpt, rengine):
        eng = rengine
        base_dl = eng.metrics.deadline_expired
        base_admit = eng.metrics.requests_admitted
        # expired while queued: failed without ever taking a slot
        rq = eng.add_request([1, 2], max_new_tokens=4, deadline_s=1e-4)
        time.sleep(0.002)
        eng.run()
        assert rq.state == "failed" and "deadline" in rq.error
        assert rq.slot is None and rq.output_ids == []
        assert eng.metrics.requests_admitted == base_admit
        # expired mid-decode: the callback makes each token cost >= 10ms,
        # so 13 tokens can never fit the 120ms budget — the request is
        # admitted, emits a few tokens, then fails on a step boundary
        rd = eng.add_request([3, 4], max_new_tokens=13, deadline_s=0.12,
                             stream_cb=lambda t, r: time.sleep(0.01))
        eng.run()
        assert rd.state == "failed" and "deadline" in rd.error
        assert 1 <= len(rd.output_ids) < 13
        assert eng.metrics.deadline_expired - base_dl == 2
        assert sorted(eng.free_slots) == [0, 1]

    def test_stream_cb_failure_isolates_request(self, gpt, rengine):
        eng = rengine
        base_fail = eng.metrics.requests_failed
        base_cb = eng.metrics.callback_errors

        def bad_cb(tok, req):
            if len(req.output_ids) >= 2:
                raise RuntimeError("user cb boom")

        rs = np.random.RandomState(11)
        p_bad = rs.randint(0, 128, (3,)).tolist()
        p_good = rs.randint(0, 128, (5,)).tolist()
        r_bad = eng.add_request(p_bad, max_new_tokens=4, stream_cb=bad_cb)
        r_good = eng.add_request(p_good, max_new_tokens=4)
        eng.run()                                   # must not raise
        assert r_bad.state == "failed"
        assert "stream_cb raised" in r_bad.error
        assert "user cb boom" in r_bad.error
        assert len(r_bad.output_ids) == 2           # token recorded, cb blew
        # the batch continued: the healthy request is untouched
        assert r_good.finished
        _assert_greedy_chain(gpt, p_good, r_good.output_ids)
        assert eng.metrics.callback_errors - base_cb == 1
        assert eng.metrics.requests_failed - base_fail == 1
        assert sorted(eng.free_slots) == [0, 1]

    def test_prefill_fault_slot_leak_regression(self, gpt, rengine):
        """ISSUE 4 satellite: a prefill failure used to lose the slot
        popped before _admit; every exit path must reclaim it."""
        eng = rengine
        base = eng.metrics.snapshot()["failures"]
        # two firings defeat the single retry -> the request fails
        eng.fault_plan = ServingFaultPlan().add(
            "serving.prefill", at_call=1, times=2)
        rs = np.random.RandomState(12)
        p = rs.randint(0, 128, (4,)).tolist()
        r = eng.add_request(p, max_new_tokens=3)
        eng.run()
        assert r.state == "failed" and "prefill failed" in r.error
        assert "injected fault" in r.error
        assert sorted(eng.free_slots) == [0, 1]     # the regression check
        # the engine is still fully serviceable afterwards
        r2 = eng.add_request(p, max_new_tokens=3)
        eng.run()
        assert r2.finished
        _assert_greedy_chain(gpt, p, r2.output_ids)
        fl = eng.metrics.snapshot()["failures"]
        assert fl["step_failures"] - base["step_failures"] == 2
        assert fl["step_retries"] - base["step_retries"] == 1
        assert fl["retries_by_point"].get("serving.prefill", 0) == 1
        assert fl["failed"] - base["failed"] == 1
        eng.fault_plan = ServingFaultPlan()         # disarm for later tests

    def test_chaos_decode_retry_and_cb_fault(self, gpt, monkeypatch):
        """ISSUE 4 acceptance: with an injected decode-step failure (one
        retry absorbs it) and a raising stream_cb, healthy requests finish
        bitwise-identical to an uninjected run, only the implicated
        request fails, no slot leaks, and zero steady-state recompiles."""
        monkeypatch.setenv("PADDLE_TPU_FT_SERVING_FAULTS",
                           "serving.decode@2,serving.stream_cb@3")
        eng = Engine(gpt, num_slots=2, max_seq=16, min_bucket=16)
        assert eng.fault_plan.armed                 # picked up from env
        eng.warmup()
        warm_misses = eng.metrics.compile_misses
        rs = np.random.RandomState(13)
        prompts = [rs.randint(0, 128, (L,)).tolist() for L in (3, 6, 4)]
        streamed = []
        reqs = [eng.add_request(p, max_new_tokens=4,
                                stream_cb=lambda t, r: streamed.append(
                                    (r.request_id, t)))
                for p in prompts]
        eng.run()
        # cb call #3 is r0's second token: r0 alone is implicated
        r0, r1, r2 = reqs
        assert r0.state == "failed" and "stream_cb raised" in r0.error
        # healthy requests: outputs identical to the uninjected greedy run
        for p, r in ((prompts[1], r1), (prompts[2], r2)):
            assert r.finished and len(r.output_ids) == 4
            # greedy chain parity == bitwise identity with the uninjected
            # run (greedy decode is deterministic)
            _assert_greedy_chain(gpt, p, r.output_ids)
            got = [t for rid, t in streamed if rid == r.request_id]
            assert got == r.output_ids
        assert sorted(eng.free_slots) == [0, 1]     # no slot leaked
        st = eng.stats()
        assert st["failures"]["failed"] == 1
        assert st["failures"]["callback_errors"] == 1
        assert st["failures"]["step_failures"] == 1     # decode call #2
        assert st["failures"]["step_retries"] == 1      # absorbed by retry
        assert st["failures"]["retries_by_point"] == {"serving.decode": 1}
        # zero steady-state compile misses through all failure handling
        assert eng.metrics.compile_misses == warm_misses
        assert st["health"]["state"] == "active"
        json.dumps(st)
        type(self).chaos_engine = eng               # reused by shutdown test

    def test_decode_retry_exhausted_fails_batch_not_engine(self, gpt,
                                                           rengine):
        eng = rengine
        base = eng.metrics.snapshot()["failures"]
        eng.fault_plan = ServingFaultPlan().add(
            "serving.decode", at_call=1, times=2)
        rs = np.random.RandomState(14)
        ps = [rs.randint(0, 128, (L,)).tolist() for L in (3, 5, 4)]
        ra = eng.add_request(ps[0], max_new_tokens=3)
        rb = eng.add_request(ps[1], max_new_tokens=3)
        rc = eng.add_request(ps[2], max_new_tokens=3)
        eng.run()
        # both attempts of the first decode failed: the whole batch (and
        # only that batch) is implicated
        for r in (ra, rb):
            assert r.state == "failed" and "decode step failed" in r.error
        # the engine survived and served the queued request afterwards
        assert rc.finished
        _assert_greedy_chain(gpt, ps[2], rc.output_ids)
        fl = eng.metrics.snapshot()["failures"]
        assert fl["failed"] - base["failed"] == 2
        assert fl["step_failures"] - base["step_failures"] == 2
        assert sorted(eng.free_slots) == [0, 1]
        eng.fault_plan = ServingFaultPlan()

    def test_drain_finishes_in_flight_then_stops(self, gpt, rengine):
        eng = rengine
        rs = np.random.RandomState(15)
        ps = [rs.randint(0, 128, (L,)).tolist() for L in (3, 7, 5)]
        reqs = [eng.add_request(p, max_new_tokens=3) for p in ps]
        st = eng.drain()
        for p, r in zip(ps, reqs):
            assert r.finished
            _assert_greedy_chain(gpt, p, r.output_ids)
        assert eng.state == "stopped"
        assert st["health"]["state"] == "stopped"
        assert st["queue_depth"] == 0 and st["requests"]["running"] == 0
        with pytest.raises(EngineStopped):
            eng.add_request([1, 2])
        with pytest.raises(EngineStopped):
            eng.warmup()

    def test_shutdown_timeout_cancels_remaining(self, gpt):
        # reuse the chaos test's engine when available (saves a compile);
        # build a fresh one so this test also runs standalone
        eng = getattr(type(self), "chaos_engine", None) or \
            Engine(gpt, num_slots=2, max_seq=16, min_bucket=16)
        r1 = eng.add_request([1, 2], max_new_tokens=14)
        r2 = eng.add_request([3, 4], max_new_tokens=14)
        eng.step()                                  # both admitted
        st = eng.shutdown(timeout_s=0.0)
        assert eng.state == "stopped"
        for r in (r1, r2):
            assert r.state == "cancelled" and r.error == "engine shutdown"
        assert sorted(eng.free_slots) == [0, 1]
        assert st["failures"]["cancelled"] >= 2
        with pytest.raises(EngineStopped):
            eng.add_request([5])
        # both lifecycle outcomes visible on the profiler health surface
        import paddle_tpu.profiler as profiler

        health = profiler.serving_health()
        assert health[eng.name]["state"] == "stopped"

    def test_watchdog_marks_engine_unhealthy(self, gpt):
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16,
                     step_timeout_s=0.1,
                     fault_plan=ServingFaultPlan().add(
                         "serving.decode", at_call=1, stall_s=0.6))
        r = eng.add_request([1, 2], max_new_tokens=4)
        with pytest.raises(EngineStopped, match="unhealthy"):
            eng.run()
        assert eng.state == "unhealthy"
        assert "watchdog" in eng.health()["reason"]
        assert eng._watchdog.fired
        # the monitor fired and exited: health must not claim protection
        assert eng.health()["watchdog_armed"] is False
        with pytest.raises(EngineStopped):
            eng.add_request([3, 4])
        import paddle_tpu.profiler as profiler

        assert profiler.serving_health()[eng.name]["state"] == "unhealthy"
        # shutdown still reclaims the in-flight request and its slot
        eng.shutdown(timeout_s=0.0)
        assert r.state == "cancelled"
        assert sorted(eng.free_slots) == [0]
        assert eng.state == "unhealthy"             # sticky: needs replace
        assert eng._watchdog is None                # thread joined, no pin

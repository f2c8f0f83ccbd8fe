"""ISSUE 15: speculative decoding — draft propose, bucketed verify,
device-side accept.

The correctness bar mirrors the rest of the serving stack:

- **Greedy is bitwise.**  A speculative engine's greedy output equals
  the non-speculative engine's, token for token — GPT and GQA-Llama —
  because every emitted greedy token IS the
  target argmax at its position, whatever the draft proposed.  The
  multi-accept path (self-speculation: draft == target) and the
  low-accept path (independent random draft) both pin it, at ZERO
  steady-state compile misses after ``warmup()``.
- **Seeded sampling is distribution-preserving.**  Rejection-sampling
  acceptance leaves every emitted position marginally the target law
  (4k-draw L1 bound against the masked target softmax, in the
  test_device_sampling style) and seeded runs replay bitwise.
- **Rollback is clean bookkeeping.**  Rejected verify positions roll
  back via the in-graph length advance + paged block-table truncation:
  the allocator audits clean mid-flight and drains to zero used blocks.
- **Speculating requests are ordinary requests.**  Preempt-resume and
  journal crash-recovery replay-from-prompt land bitwise on the
  uninterrupted run, exactly once, with flat compile counters.

NOTHING here may be marked slow — tools/collect_gate.py enforces this
module rides in tier-1.
"""
import os
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (
    GPTForCausalLM, LlamaForCausalLM, gpt_tiny, llama_tiny,
)
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.serving import (
    Engine, RequestJournal, RequestTracer, SamplingParams, SpecConfig,
    validate_trace,
)
from paddle_tpu.serving.sampling import (
    DeviceSampler, _device_masked_logits,
)

K = 3                      # draft tokens per round in every engine here
ENG = dict(num_slots=2, max_seq=32, min_bucket=16)
PAGED = dict(block_size=8)

rs = np.random.RandomState(0)
PROMPTS = [rs.randint(0, 128, (L,)).tolist() for L in (5, 13, 9, 3)]


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt_draft():
    # an INDEPENDENT 1-layer draft: proposals mostly rejected — the
    # verification/rollback machinery is exercised, and greedy output
    # must STILL be bitwise (emitted tokens are target argmaxes)
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama():
    paddle.seed(0)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def llama_draft():
    paddle.seed(9)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, num_key_value_heads=1,
        intermediate_size=64, max_position_embeddings=64))
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt_ref(gpt):
    """Non-speculative greedy oracle: no draft, and the jnp gather
    attention in place of the Pallas kernels."""
    eng = Engine(gpt, **ENG, kernel="reference")
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def llama_ref(llama):
    eng = Engine(llama, **ENG, kernel="reference")
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def gpt_spec_paged(gpt, gpt_draft):
    """The workhorse: paged speculative GPT engine with a tracer (the
    chain/exporter tests validate the SAME traffic the parity tests
    pay for)."""
    eng = Engine(gpt, **ENG, **PAGED, tracer=RequestTracer(),
                 speculation=SpecConfig(draft_model=gpt_draft, k=K))
    eng.warmup()
    return eng


def _generate(eng, prompts=PROMPTS, n=10, **kw):
    reqs = [eng.add_request(p, max_new_tokens=n, **kw) for p in prompts]
    eng.run()
    assert all(r.finished for r in reqs), \
        [(r.state, r.error) for r in reqs]
    return [r.output_ids for r in reqs]


# -- greedy bitwise parity ---------------------------------------------------

class TestGreedyBitwise:
    def test_gpt_paged_low_accept(self, gpt_ref, gpt_spec_paged):
        base = _generate(gpt_ref)
        m0 = gpt_spec_paged.metrics.compile_misses
        out = _generate(gpt_spec_paged)
        assert out == base
        # zero steady-state compile misses: warmup covered draft +
        # verify programs too (the generalized-warmup satellite)
        assert gpt_spec_paged.metrics.compile_misses == m0
        st = gpt_spec_paged.stats()["speculation"]
        assert st["rounds"] > 0 and st["proposed"] > 0
        assert st["verify_steps"] == st["rounds"]
        assert st["draft_steps"] == K * st["rounds"]

    def test_gpt_self_spec_full_accept(self, gpt, gpt_ref):
        # the full-accept regime (on one-block slots: block_size 16):
        # draft == target means (near-)every proposal is accepted — the
        # multi-token advance + draft-KV-lockstep path, still bitwise
        base = _generate(gpt_ref)
        eng = Engine(gpt, **ENG,
                     speculation=SpecConfig(draft_model=gpt, k=K))
        eng.warmup()
        m0 = eng.metrics.compile_misses
        assert _generate(eng) == base
        assert eng.metrics.compile_misses == m0
        st = eng.stats()["speculation"]
        assert st["accept_rate"] > 0.5      # budget caps trim the tail
        assert st["mean_accepted_per_round"] > 0

    def test_llama_gqa_paged(self, llama, llama_draft, llama_ref):
        assert llama.config.n_kv_heads < llama.config.num_attention_heads
        base = _generate(llama_ref, n=8)
        eng = Engine(llama, **ENG, **PAGED,
                     speculation=SpecConfig(draft_model=llama_draft, k=K))
        eng.warmup()
        m0 = eng.metrics.compile_misses
        assert _generate(eng, n=8) == base
        assert eng.metrics.compile_misses == m0

    def test_eos_mid_round_stops_like_nospec(self, gpt_ref,
                                             gpt_spec_paged):
        # pick the reference's 3rd generated token as eos: both engines
        # must truncate identically even when the speculative round
        # overshoots the stop token
        base = _generate(gpt_ref, prompts=[PROMPTS[0]], n=10)[0]
        eos = base[2]
        want = base[:base.index(eos) + 1]
        for eng in (gpt_ref, gpt_spec_paged):
            out = _generate(eng, prompts=[PROMPTS[0]], n=10,
                            eos_token_id=eos)[0]
            assert out == want, eng.name

    def test_capacity_retire_near_max_seq(self, gpt, gpt_ref,
                                          gpt_spec_paged):
        # a prompt 3 short of max_seq: the verify window overhangs the
        # cache end (scatter-dropped / scratch-masked writes) and the
        # request retires on capacity exactly like non-spec
        prompt = rs.randint(0, 128, (29,)).tolist()
        for eng in (gpt_ref, gpt_spec_paged):
            r = eng.add_request(prompt, max_new_tokens=16)
            eng.run()
            assert r.finished
        base = _generate(gpt_ref, prompts=[prompt], n=16)
        assert _generate(gpt_spec_paged, prompts=[prompt], n=16) == base

    def test_max_seq_prompt_retires_at_first_token(self, gpt_ref,
                                                   gpt_spec_paged):
        # a prompt of exactly max_seq: _done_after_emit retires it when
        # the prefill token is delivered, BEFORE any round runs — so a
        # speculative engine never dispatches a verify window it has no
        # cache room for, and the outputs match the plain engine's
        prompt = rs.randint(0, 128, (32,)).tolist()
        rounds0 = gpt_spec_paged.metrics.spec_rounds
        base = _generate(gpt_ref, prompts=[prompt], n=4)
        assert _generate(gpt_spec_paged, prompts=[prompt], n=4) == base
        assert len(base[0]) == 1
        assert gpt_spec_paged.metrics.spec_rounds == rounds0


# -- seeded sampling ---------------------------------------------------------

class TestSeededSampling:
    def test_accept_marginal_matches_target_law(self):
        """4k seeded rounds through accept_speculative (vectorized as
        4k sampler slots — ONE batched call): the FIRST emitted token's
        empirical distribution must match the masked target softmax
        (the rejection-sampling identity) even though the draft
        proposes from a very different law."""
        lrs = np.random.RandomState(1)
        V, k, N = 24, 3, 4000
        tlog = (lrs.randn(1, k + 1, V) * 2).astype(np.float32)
        dlog = (lrs.randn(1, k + 1, V) * 2).astype(np.float32)
        tgt, drf = DeviceSampler(N), DeviceSampler(N)
        for s, base in ((tgt, 1000), (drf, 500_000)):
            s.keys._set_data(jax.vmap(jax.random.PRNGKey)(
                jnp.arange(base, base + N)).astype(jnp.uint32))
            s.temps._set_data(jnp.full((N,), 0.8, jnp.float32))
            s.top_ks._set_data(jnp.full((N,), 8, jnp.int32))
            s.top_ps._set_data(jnp.full((N,), 0.9, jnp.float32))
        zd = _device_masked_logits(
            jnp.asarray(dlog[0, :k]), jnp.full((k,), 0.8),
            jnp.full((k,), 8, jnp.int32), jnp.full((k,), 0.9))
        dk = jax.vmap(lambda i: jax.random.split(
            jax.random.PRNGKey(i), k))(jnp.arange(N))     # [N, k, 2]
        dtoks = jnp.stack(
            [jax.vmap(jax.random.categorical, in_axes=(0, None))(
                dk[:, j], zd[j]) for j in range(k)],
            axis=1).astype(jnp.int32)                     # [N, k]
        emitted, m = tgt.accept_speculative(
            jnp.broadcast_to(jnp.asarray(tlog), (N, k + 1, V)),
            jnp.broadcast_to(jnp.asarray(dlog), (N, k + 1, V)),
            dtoks, jnp.full((N,), k + 1, jnp.int32), drf)
        m = np.asarray(m)
        assert np.all((m >= 1) & (m <= k + 1))
        counts = np.bincount(np.asarray(emitted[:, 0]), minlength=V)
        zt = _device_masked_logits(
            jnp.asarray(tlog[0, :1]), jnp.full((1,), 0.8),
            jnp.full((1,), 8, jnp.int32), jnp.full((1,), 0.9))
        pt = np.asarray(jax.nn.softmax(zt[0]))
        assert float(np.abs(counts / N - pt).sum()) < 0.05

    def test_identical_laws_degenerate_residual(self):
        # draft law == target law: every rejection residual is all-zero
        # and must fall back to the target law, never NaN/crash
        lrs = np.random.RandomState(2)
        V, k = 16, 2
        log = (lrs.randn(2, k + 1, V) * 2).astype(np.float32)
        tgt, drf = DeviceSampler(2), DeviceSampler(2)
        for slot in range(2):
            tgt.stage_slot(slot, SamplingParams(temperature=1.0), 3)
            drf.stage_slot(slot, SamplingParams(temperature=1.0), 4)
        emitted, m = tgt.accept_speculative(
            jnp.asarray(log), jnp.asarray(log),
            jnp.zeros((2, k), jnp.int32),
            jnp.full((2,), k + 1, jnp.int32), drf)
        assert np.all((np.asarray(m) >= 1) & (np.asarray(m) <= k + 1))
        assert np.all((np.asarray(emitted) >= 0)
                      & (np.asarray(emitted) < V))

    def test_seeded_replay_bitwise(self, gpt_spec_paged):
        # two seeded runs through the same warm engine: every admission
        # re-seeds both the target AND draft key lanes (stage_slot), so
        # the whole speculative process replays bitwise.  The CROSS-
        # engine half of the contract is pinned by the journal-recovery
        # test below (fresh engine, same seeded output).
        outs = [_generate(gpt_spec_paged, n=8,
                          sampling=SamplingParams(temperature=0.9,
                                                  top_k=20, top_p=0.9,
                                                  seed=42))
                for _ in range(2)]
        assert outs[0] == outs[1]


# -- KV rollback / allocator hygiene ----------------------------------------

class TestRollback:
    def test_allocator_clean_zero_leaked_blocks(self, gpt_spec_paged):
        eng = gpt_spec_paged
        reqs = [eng.add_request(p, max_new_tokens=10) for p in PROMPTS]
        seen_rounds = eng.metrics.spec_rounds
        while eng.step():
            # mid-flight: the pool must audit clean between rounds
            # (truncation dropped the rejected tail's blocks already)
            assert eng.cache.check_invariants() == []
        assert all(r.finished for r in reqs)
        assert eng.metrics.spec_rounds > seen_rounds
        st = eng.cache.allocator.stats()
        assert eng.cache.allocator.check() == []
        assert st["used"] == 0, st     # every block drained on retire
        assert eng.stats()["health"]["kv_block_invariants"] == "ok"

    def test_truncate_blocks_unit(self):
        from paddle_tpu.serving.paging import PagedKVCache, SCRATCH_BLOCK

        c = PagedKVCache(num_slots=1, num_layers=1, max_seq=32,
                         num_kv_heads=1, head_dim=4, block_size=8)
        assert c.begin_sequence(0, [], 0, 32)       # 4 blocks
        assert c.truncate_blocks(0, 17) == 1        # ceil(17/8) = 3 kept
        assert len(c.owned_blocks(0)) == 3
        assert int(c.block_tables.numpy()[0, 3]) == SCRATCH_BLOCK
        assert c.truncate_blocks(0, 17) == 0        # idempotent
        assert c.allocator.check() == []
        c.release_slot(0)
        assert c.allocator.stats()["used"] == 0


# -- preemption / durability -------------------------------------------------

SEEDED = dict(sampling=SamplingParams(temperature=0.8, top_k=12, seed=9))


class TestPreemptAndRecovery:
    """Two shared one-slot paged spec engines: ``eng_a`` serves the
    uninterrupted baseline and later the crash-abandoned attempt;
    ``eng_b`` serves the preempt-resume run and later the journal
    recovery (cross-ENGINE seeded bitwise — the crash contract)."""

    @pytest.fixture(scope="class")
    def engines(self, gpt, gpt_draft):
        def build():
            eng = Engine(gpt, num_slots=1, max_seq=32, min_bucket=16,
                         **PAGED,
                         speculation=SpecConfig(draft_model=gpt_draft,
                                                k=K))
            eng.warmup()
            return eng

        return build(), build()

    @pytest.fixture(scope="class")
    def long_prompt(self):
        return np.random.RandomState(3).randint(0, 128, (16,)).tolist()

    @pytest.fixture(scope="class")
    def baseline(self, engines, long_prompt):
        r = engines[0].add_request(long_prompt, max_new_tokens=12,
                                   **SEEDED)
        engines[0].run()
        assert r.finished
        return list(r.output_ids)

    def test_preempt_resume_bitwise(self, engines, long_prompt,
                                    baseline):
        eng = engines[1]
        victim = eng.add_request(long_prompt, max_new_tokens=12,
                                 priority="low", **SEEDED)
        for _ in range(2):
            eng.step()                   # mid-speculation
        m0 = eng.metrics.compile_misses
        hi = eng.add_request(PROMPTS[3], max_new_tokens=4,
                             priority="high")
        eng.run()
        assert hi.finished and victim.finished
        assert victim.preemptions == 1
        assert victim.output_ids == baseline
        assert eng.metrics.compile_misses == m0
        assert eng.cache.allocator.check() == []

    def test_journal_recover_bitwise_exactly_once(self, engines,
                                                  long_prompt, baseline):
        e1, e2 = engines
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "jrnl")
            j1 = RequestJournal(path)
            e1.journal = j1
            r1 = e1.add_request(long_prompt, max_new_tokens=12, **SEEDED)
            for _ in range(2):
                e1.step()                # abandon mid-speculation
            assert 0 < len(r1.output_ids) < 12
            e1.journal = None            # "crash": nothing more recorded
            j1.close()

            j2 = RequestJournal(path)
            info = e2.recover(j2)
            assert info["replayed"] == 1
            m0 = e2.metrics.compile_misses
            e2.run()
            rr = info["requests"][0]
            assert rr.finished and rr.recovered
            assert rr.output_ids == baseline
            assert e2.metrics.compile_misses == m0
            assert j2.audit()["duplicate_terminals"] == 0
            e2.journal = None
            j2.close()

    def test_journal_burst_records_round_trip(self):
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "jrnl")
            j = RequestJournal(path)
            j.record_admission(
                "e:b0:r0", prompt_ids=[1, 2], sampling={},
                seed_effective=7, priority=1, deadline_s=None,
                max_new_tokens=8, eos_token_id=None, engine="e",
                model_version=0)
            j.record_tokens("e", 0, {"e:b0:r0": 5})          # plain step
            j.record_tokens("e", 1, {"e:b0:r0": [6, 7, 8]})  # spec burst
            j.close()
            j2 = RequestJournal(path)
            assert j2.tokens_for("e:b0:r0") == [5, 6, 7, 8]
            j2.close()


# -- observability -----------------------------------------------------------

class TestObservability:
    def test_trace_chain_valid_with_verify_events(self, gpt_spec_paged):
        tr = gpt_spec_paged.tracer
        assert validate_trace(tr) == []
        vs = [e for e in tr.events if e["kind"] == "verify_step"]
        assert vs, "no verify_step events recorded"
        # decode_step discipline: one event per ROUND, never per token
        assert all("proposed" in e and "accepted" in e
                   and e["n_active"] >= 1 for e in vs)
        assert not any(e["kind"] == "decode_step" for e in tr.events)

    def test_perfetto_accepted_tokens_counter_track(self,
                                                    gpt_spec_paged):
        from paddle_tpu.obs import chrome_trace

        trace = chrome_trace(gpt_spec_paged.tracer)
        names = {e["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "C"}
        assert "accepted_tokens" in names and "active_slots" in names

    def test_speculation_stats_and_exposition(self, gpt_spec_paged):
        from paddle_tpu.obs.metrics import render_metrics

        st = gpt_spec_paged.stats()
        sp = st["speculation"]
        assert sp["k"] == K and sp["rounds"] > 0
        assert 0.0 <= sp["accept_rate"] <= 1.0
        assert sp["proposed"] >= sp["accepted"] >= 0
        text = render_metrics(st)
        assert "speculation_rounds" in text
        assert "speculation_accept_rate" in text

    def test_warmup_registry_covers_draft_and_verify(self, gpt_ref,
                                                     gpt_spec_paged):
        # re-warming the already-warm fixtures is pure cache hits: the
        # registry listing and the flat miss counter are the proof that
        # warmup() covers every program set (target + draft + verify)
        m0 = gpt_spec_paged.metrics.compile_misses
        info = gpt_spec_paged.warmup()
        assert info["programs"] == ["prefill", "draft_prefill",
                                    "draft_decode", "verify"]
        assert gpt_spec_paged.metrics.compile_misses == m0
        # non-spec engines keep the plain registry (back-compat)
        assert gpt_ref.warmup()["programs"] == ["prefill", "decode"]


class TestConfigValidation:
    def test_vocab_mismatch_rejected(self, gpt):
        bad = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=64))
        with pytest.raises(ValueError, match="vocab"):
            Engine(gpt, **ENG,
                   speculation=SpecConfig(draft_model=bad, k=K))

    def test_k_validated(self, gpt):
        with pytest.raises(ValueError, match="k must be >= 1"):
            SpecConfig(draft_model=gpt, k=0)

    def test_short_draft_positions_rejected(self, gpt):
        bad = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=16))
        with pytest.raises(ValueError, match="max_position_embeddings"):
            Engine(gpt, **ENG,
                   speculation=SpecConfig(draft_model=bad, k=K))

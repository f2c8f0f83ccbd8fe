"""ISSUE 20: multi-tenant serving — per-request LoRA adapter lanes,
constrained decoding, and per-tenant accounting.

The correctness bar follows the serving stack's house rules:

- **Tenancy is invisible until used.**  An engine with adapter + grammar
  lanes enabled but no adapter selected produces BITWISE the plain
  engine's outputs (the lane-0 base row is a where-select, not a
  ``+ 0.0`` that could flip signed zeros), at zero steady-state compile
  misses — adapter ids and grammar states are data, never trace
  constants, so one warmed executable set serves every tenant mix.
- **Tenants are isolated.**  Two adapters produce different outputs from
  the same prompt, a mixed batch keeps the base rows bitwise, and the
  prefix cache is salted per adapter version: identical prompt bytes
  live in disjoint hash domains, so tenant KV can never cross-hit.
- **Constrained decoding is sound.**  Grammar-masked greedy emits
  token-valid JSON (host DFA oracle), composes with speculative
  verify bitwise, and the masks ride inside the compiled programs.
- **Tenant requests are ordinary requests.**  Preempt-resume and
  journal crash-recovery land bitwise on the uninterrupted run; an
  adapter unloaded mid-flight fails its requests with machine-readable
  ``error_ctx`` and never wedges the engine or the recovery loop.
- **Sharding changes nothing.**  A model=2 engine with the same
  adapters/grammars is bitwise the single-chip tenancy engine.

NOTHING here may be marked slow — tools/collect_gate.py enforces this
module rides in tier-1.
"""
import os
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.serving import (
    Engine, JsonArrayGrammar, RequestJournal, SamplingParams,
    SpecConfig, make_lora_weights, serving_mesh,
)

ENG = dict(num_slots=2, max_seq=32, min_bucket=16)
PAGED = dict(block_size=8)
SPEC = JsonArrayGrammar(eos_token_id=1, max_elems=3, max_digits=2)
# adapters= and grammars= are plain-dict engine kwargs so Fleet replicas
# can clone them; init_scale 0.5 makes the tiny model's argmax actually
# move (the default 0.02 perturbs logits below greedy margins)
TEN = dict(adapters=dict(max_adapters=2, rank=4),
           grammars={"json": SPEC})
SCALE = 0.5

rs = np.random.RandomState(0)
PROMPTS = [rs.randint(0, 128, (L,)).tolist() for L in (5, 13, 9)]


def _load(eng, names=("t1", "t2")):
    for i, name in enumerate(names, start=1):
        eng.load_adapter(name, make_lora_weights(
            eng.adapter_pool, seed=i, init_scale=SCALE))


def _generate(eng, prompts=PROMPTS, n=8, **kw):
    reqs = [eng.add_request(p, max_new_tokens=n, **kw) for p in prompts]
    eng.run()
    assert all(r.finished for r in reqs), \
        [(r.state, r.error, r.error_ctx) for r in reqs]
    return [r.output_ids for r in reqs]


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def plain_ref(gpt):
    """The pre-tenancy oracle: a plain engine with NO lanes compiled."""
    eng = Engine(gpt, **ENG)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def ten_eng(gpt):
    """The workhorse: paged tenancy engine, both adapters loaded,
    preemption armed — shared by every test that doesn't mutate the
    adapter registry."""
    eng = Engine(gpt, **ENG, **PAGED, **TEN,
                 max_preemptions=2, priority_aging_s=30.0)
    eng.warmup()
    _load(eng)
    return eng


# ---------------------------------------------------------------------------
# adapter-off bitwise + flat counters
# ---------------------------------------------------------------------------

class TestAdapterOffBitwise:
    def test_lanes_off_equals_plain_engine(self, plain_ref, ten_eng):
        """Adapters loaded but NOT selected: outputs bitwise equal the
        engine that never compiled a lane."""
        base = _generate(plain_ref)
        m0 = ten_eng.metrics.compile_misses
        assert _generate(ten_eng) == base
        assert ten_eng.metrics.compile_misses == m0


# ---------------------------------------------------------------------------
# tenant isolation
# ---------------------------------------------------------------------------

class TestTenantIsolation:
    def test_adapters_differ_and_mixed_batch_is_clean(self, plain_ref,
                                                      ten_eng):
        base = _generate(plain_ref)
        m0 = ten_eng.metrics.compile_misses
        a1 = _generate(ten_eng, sampling=SamplingParams(adapter="t1"))
        a2 = _generate(ten_eng, sampling=SamplingParams(adapter="t2"))
        assert a1 != base and a2 != base and a1 != a2
        # base + adapter sharing one decode batch: the lane-0 rows stay
        # bitwise (the where-select guards the base law, not just its
        # magnitude)
        rb = ten_eng.add_request(PROMPTS[0], max_new_tokens=8)
        rt = ten_eng.add_request(PROMPTS[1], max_new_tokens=8,
                                 sampling=SamplingParams(adapter="t1"))
        ten_eng.run()
        assert rb.output_ids == base[0]
        assert rt.output_ids == a1[1]
        assert ten_eng.metrics.compile_misses == m0
        # per-tenant accounting saw every class
        by = ten_eng.metrics.snapshot()["tenants"]["by_tenant"]
        assert by["t1"]["completed"] >= 1 and by["t2"]["completed"] >= 1
        assert by["base"]["completed"] >= 1
        assert by["t1"]["ttft_ms"]["p50"] > 0

    def test_prefix_domains_disjoint_per_adapter(self, ten_eng):
        """Identical prompt bytes, three salt domains: KV registered
        under one tenant must be invisible to every other."""
        prompt = np.random.RandomState(11).randint(
            0, 128, (16,)).tolist()
        _generate(ten_eng, prompts=[prompt], n=4,
                  sampling=SamplingParams(adapter="t1"))
        assert ten_eng.prefix_probe(prompt, adapter="t1") > 0
        assert ten_eng.prefix_probe(prompt) == 0
        assert ten_eng.prefix_probe(prompt, adapter="t2") == 0
        _generate(ten_eng, prompts=[prompt], n=4)
        assert ten_eng.prefix_probe(prompt) > 0


# ---------------------------------------------------------------------------
# constrained decoding
# ---------------------------------------------------------------------------

class TestGrammar:
    def test_greedy_emits_valid_json(self, ten_eng):
        m0 = ten_eng.metrics.compile_misses
        outs = _generate(ten_eng, n=SPEC.max_tokens,
                         sampling=SamplingParams(grammar="json"))
        for o in outs:
            assert SPEC.accepts(o, 128), o
        assert ten_eng.metrics.compile_misses == m0

    def test_masks_compose_with_sampling_laws(self, ten_eng):
        """Grammar masks under temperature/top-k: still valid JSON —
        the mask applies BEFORE the sampling law, whatever the law."""
        outs = _generate(
            ten_eng, n=SPEC.max_tokens,
            sampling=SamplingParams(grammar="json", temperature=0.8,
                                    top_k=8, seed=3))
        for o in outs:
            assert SPEC.accepts(o, 128), o

    def test_validation_rejects_unknown_tenants(self, gpt, ten_eng):
        with pytest.raises(ValueError, match="not loaded"):
            ten_eng.add_request(PROMPTS[0], max_new_tokens=4,
                                sampling=SamplingParams(adapter="nope"))
        with pytest.raises(ValueError, match="grammar"):
            ten_eng.add_request(PROMPTS[0], max_new_tokens=4,
                                sampling=SamplingParams(grammar="yaml"))
        # tenancy params against an engine with no lanes at all
        with pytest.raises(ValueError, match="adapter"):
            Engine(gpt, **ENG).add_request(
                PROMPTS[0], max_new_tokens=4,
                sampling=SamplingParams(adapter="t1"))


# ---------------------------------------------------------------------------
# speculative decoding with masks + adapters
# ---------------------------------------------------------------------------

class TestSpeculativeTenancy:
    def test_spec_greedy_bitwise_all_classes(self, gpt, ten_eng):
        """A speculative tenancy engine (independent 1-layer draft, so
        rejections actually happen) is greedy-bitwise with the plain
        tenancy engine for base, adapter, and grammar classes — masks
        apply to both draft and target laws, adapters to the target
        only."""
        paddle.seed(7)
        draft = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=64,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
        draft.eval()
        eng = Engine(gpt, **ENG, **PAGED, **TEN,
                     speculation=SpecConfig(draft_model=draft, k=3))
        eng.warmup()
        _load(eng)
        m0 = eng.metrics.compile_misses
        assert _generate(eng) == _generate(ten_eng)
        assert _generate(eng, sampling=SamplingParams(adapter="t1")) \
            == _generate(ten_eng, sampling=SamplingParams(adapter="t1"))
        g = SamplingParams(grammar="json")
        assert _generate(eng, n=SPEC.max_tokens, sampling=g) \
            == _generate(ten_eng, n=SPEC.max_tokens, sampling=g)
        assert eng.metrics.compile_misses == m0
        assert eng.stats()["speculation"]["rounds"] > 0


# ---------------------------------------------------------------------------
# preemption + crash recovery
# ---------------------------------------------------------------------------

class TestTenantDurability:
    def test_preempted_tenant_resumes_bitwise(self, ten_eng):
        want = _generate(ten_eng, prompts=PROMPTS[:2], n=8,
                         sampling=SamplingParams(adapter="t1"))
        m0 = ten_eng.metrics.compile_misses
        lo = [ten_eng.add_request(p, max_new_tokens=8, priority="low",
                                  sampling=SamplingParams(adapter="t1"))
              for p in PROMPTS[:2]]
        ten_eng.step()
        ten_eng.step()
        assert all(r.state == "running" for r in lo)
        hi = ten_eng.add_request(PROMPTS[2], max_new_tokens=4,
                                 priority="high")
        ten_eng.run()
        assert any(r.preempted for r in lo) and hi.finished
        assert [r.output_ids for r in lo] == want
        assert ten_eng.metrics.compile_misses == m0

    def test_crash_recovery_replays_tenant_bitwise(self, gpt):
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "journal")
            e1 = Engine(gpt, **ENG, **TEN, journal=RequestJournal(path))
            e1.warmup()
            _load(e1, names=("t1",))
            want = _generate(e1, prompts=[PROMPTS[0]],
                             sampling=SamplingParams(adapter="t1"))[0]
            gwant = _generate(e1, prompts=[PROMPTS[1]],
                              n=SPEC.max_tokens,
                              sampling=SamplingParams(grammar="json"))[0]
            r1 = e1.add_request(PROMPTS[0], max_new_tokens=8,
                                sampling=SamplingParams(adapter="t1"))
            r2 = e1.add_request(PROMPTS[1],
                                max_new_tokens=SPEC.max_tokens,
                                sampling=SamplingParams(grammar="json"))
            e1.step()                      # in flight, then "crash"
            assert r1.adapter_version == 1
            e1.journal.close()

            e2 = Engine(gpt, **ENG, **TEN, journal=RequestJournal(path))
            e2.warmup()
            _load(e2, names=("t1",))
            res = e2.recover()
            assert res["replayed"] == 2 and not res["invalid"]
            m0 = e2.metrics.compile_misses
            e2.run()
            outs = {tuple(r.prompt_ids): r.output_ids
                    for r in res["requests"]}
            assert outs[tuple(PROMPTS[0])] == want
            assert outs[tuple(PROMPTS[1])] == gwant
            assert e2.metrics.compile_misses == m0

            # registry mutated under the journal: unload t1, recover a
            # fresh crash — the replay fails FINALLY (journal converges)
            # instead of serving different weights or wedging
            e2.journal.close()
            e3 = Engine(gpt, **ENG, **TEN, journal=RequestJournal(path))
            e3.warmup()
            _load(e3, names=("t1",))
            e3.add_request(PROMPTS[0], max_new_tokens=8,
                           sampling=SamplingParams(adapter="t1"))
            e3.step()
            e3.journal.close()
            e4 = Engine(gpt, **ENG, **TEN, journal=RequestJournal(path))
            e4.warmup()                    # t1 deliberately NOT loaded
            res = e4.recover()
            assert len(res["invalid"]) == 1 and res["replayed"] == 0
            assert not e4.journal.pending()

    def test_unload_fails_inflight_with_error_ctx(self, gpt):
        eng = Engine(gpt, **ENG, **TEN)
        eng.warmup()
        _load(eng, names=("t1",))
        r = eng.add_request(PROMPTS[0], max_new_tokens=20,
                            sampling=SamplingParams(adapter="t1"))
        eng.step()
        eng.step()
        assert r.state == "running"
        eng.unload_adapter("t1")
        assert r.state == "failed"
        assert r.error_ctx == {"adapter": "t1", "version": 1}
        # hot-swap (load over a live name) is the same torn-hybrid
        # hazard: re-load, start a request, swap — it must fail too
        _load(eng, names=("t1",))
        r2 = eng.add_request(PROMPTS[0], max_new_tokens=20,
                             sampling=SamplingParams(adapter="t1"))
        eng.step()
        eng.load_adapter("t1", make_lora_weights(
            eng.adapter_pool, seed=9, init_scale=SCALE))
        assert r2.state == "failed"
        assert r2.error_ctx == {"adapter": "t1", "version": 2}
        # the engine itself is unharmed: base traffic still serves
        assert _generate(eng, prompts=[PROMPTS[2]], n=4)[0]
        # ... and the NEW version serves under a fresh version pin
        r3 = eng.add_request(PROMPTS[0], max_new_tokens=4,
                             sampling=SamplingParams(adapter="t1"))
        eng.run()
        assert r3.finished and r3.adapter_version == 3


# ---------------------------------------------------------------------------
# sharded parity
# ---------------------------------------------------------------------------

class TestShardedTenancy:
    def test_mp2_bitwise_parity(self, gpt, plain_ref, ten_eng):
        """model=2 tenancy engine: adapter banks shard with the layers
        they modify (column B / row A over the model axis), lanes and
        grammar tables replicate — outputs bitwise the single-chip
        tenancy engine for every class, zero steady-state misses."""
        m = GPTForCausalLM(gpt.config)
        m.eval()
        m.set_state_dict(gpt.state_dict())
        eng = Engine(m, mesh=serving_mesh(2), **ENG, **PAGED, **TEN)
        eng.warmup()
        _load(eng)
        m0 = eng.metrics.compile_misses
        assert _generate(eng) == _generate(plain_ref)
        assert _generate(eng, sampling=SamplingParams(adapter="t1")) \
            == _generate(ten_eng, sampling=SamplingParams(adapter="t1"))
        g = SamplingParams(grammar="json")
        assert _generate(eng, n=SPEC.max_tokens, sampling=g) \
            == _generate(ten_eng, n=SPEC.max_tokens, sampling=g)
        assert eng.metrics.compile_misses == m0
        snap = eng.stats()
        assert snap["sharding"]["model_parallel"] == 2
        assert snap["tenancy"]["adapters"] == {"t1": 1, "t2": 1}

"""Tensor-parallel sharded serving (ISSUE 18).

The acceptance bar is BITWISE: an ``Engine(mesh=serving_mesh(2))`` on a
host-device mesh (conftest forces 8 CPU devices) must produce greedy
output identical to the single-chip engine — for GPT (MHA) and Llama
(GQA) — at zero steady-state recompiles, and every
engine subsystem (speculation, preempt/resume, journal recovery, fleet
hot swap) must survive sharding unchanged.  Mesh size 1 must degenerate
to the unsharded engine exactly.

Budget discipline: single-chip baseline outputs (the unsharded
``kernel="reference"`` engine: no mesh, no Pallas kernel, no draft) are
computed once per family and cached module-wide; every sharded engine is slim
(2 slots, ONE 16-wide prefill bucket, 3 prompts, 6 new tokens — prompt
lengths chosen to cross a block_size=8 boundary while prompt+decode
still fits the single bucket).  Tier-1 critical:
tools/collect_gate.py fails CI if this file stops collecting or grows a
``slow`` mark.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (
    GPTForCausalLM, LlamaForCausalLM, gpt_tiny, llama_tiny,
)
from paddle_tpu.serving import (
    Engine, Fleet, RequestJournal, SpecConfig, serving_mesh,
    mesh_shape_key,
)
from paddle_tpu.serving.sharding import KV_LAYER_SPEC, ServingShard

_FAMILIES = {
    "gpt": (GPTForCausalLM, gpt_tiny),
    "llama": (LlamaForCausalLM, llama_tiny),
}

ENGINE_KW = dict(num_slots=2, max_seq=16, min_bucket=16)   # one block a slot
PAGED_KW = dict(ENGINE_KW, block_size=8, num_kv_blocks=24)
MAX_NEW = 6

_rs = np.random.RandomState(3)
PROMPTS = [_rs.randint(0, 128, (L,)).tolist() for L in (5, 9, 10)]


@pytest.fixture(scope="module")
def models():
    out = {}
    for tag, (cls, cfgfn) in _FAMILIES.items():
        paddle.seed(0)
        m = cls(cfgfn())
        m.eval()
        out[tag] = m
    return out


def _clone(src):
    m = type(src)(src.config)
    m.eval()
    m.set_state_dict(src.state_dict())
    return m


def _assert_greedy_chain(model, prompt, out_ids):
    """``out_ids`` must BE the no-cache greedy generation for ``prompt``
    (one full causal forward per check — no extra engine warmup)."""
    full = list(prompt) + [int(t) for t in out_ids]
    with paddle.no_grad():
        logits = model(paddle.to_tensor(
            np.asarray(full[:-1], np.int64)[None])).numpy()[0]
    L = len(prompt)
    for i, t in enumerate(out_ids):
        assert int(np.argmax(logits[L - 1 + i])) == int(t), (i, t)


@pytest.fixture(scope="module")
def baseline(models):
    """Single-chip greedy outputs of the ``kernel="reference"`` engine,
    computed once per family."""
    cache = {}

    def get(tag):
        if tag not in cache:
            eng = Engine(_clone(models[tag]), kernel="reference",
                         **PAGED_KW)
            eng.warmup()
            cache[tag] = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)
        return cache[tag]

    return get


# ---------------------------------------------------------------------------
# bitwise parity
# ---------------------------------------------------------------------------

class TestShardedParity:
    @pytest.mark.parametrize("tag", ["gpt", "llama"])
    # one case: ``[paged-*]`` are the ids earlier PRs' records know
    @pytest.mark.parametrize("layout", ["paged"])
    def test_sharded_bitwise_parity(self, models, baseline, tag, layout):
        """model-axis-2 greedy decode == single-chip, MHA and GQA
        (llama_tiny: 2 kv heads, one whole GQA group per shard), with
        zero steady-state compile misses."""
        eng = Engine(_clone(models[tag]), mesh=serving_mesh(2), **PAGED_KW)
        eng.warmup()
        warm = eng.metrics.compile_misses
        out = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)
        assert out == baseline(tag)
        assert eng.metrics.compile_misses == warm
        # the sharded state really is sharded: kv_heads (dim 2 of each of
        # the pool's per-layer buffers) split over the model axis, every
        # other dim whole (JAX drops the trailing Nones of the stored
        # spec)
        for buf in (*eng.cache.k, *eng.cache.v):
            spec = tuple(buf._value().sharding.spec)
            assert tuple(KV_LAYER_SPEC)[:len(spec)] == spec
            assert spec[2] == "model"
        snap = eng.stats()
        assert snap["sharding"] == {"mesh_shape": "model=2",
                                    "model_parallel": 2}

    def test_mesh_size_one_degenerates_exactly(self, models, baseline):
        """serving_mesh(1) is the unsharded engine: outputs bitwise
        equal, every placement filtered to replicated."""
        eng = Engine(_clone(models["gpt"]), mesh=serving_mesh(1),
                     **ENGINE_KW)
        eng.warmup()
        assert eng.generate(PROMPTS, max_new_tokens=MAX_NEW) == \
            baseline("gpt")
        # size-1 axis filters out of every spec → fully replicated state
        assert all(s is None for buf in eng.cache.buffers()
                   for s in tuple(buf._value().sharding.spec))
        assert eng.mesh_shape == "model=1"

    def test_sharded_speculative_decoding_parity(self, models, baseline):
        """Speculation survives sharding: draft model/cache/sampler and
        the proposals lane are placed on the serving mesh, and because
        spec greedy is bitwise plain greedy (the spec_decode contract),
        the sharded speculative output must equal the single-chip
        non-speculative baseline."""
        paddle.seed(7)
        draft = GPTForCausalLM(gpt_tiny())
        draft.eval()
        eng = Engine(_clone(models["gpt"]), mesh=serving_mesh(2),
                     speculation=SpecConfig(draft_model=draft, k=3),
                     **ENGINE_KW)
        eng.warmup()
        warm = eng.metrics.compile_misses
        out = eng.generate(PROMPTS, max_new_tokens=MAX_NEW)
        assert out == baseline("gpt")
        assert eng.metrics.compile_misses == warm


# ---------------------------------------------------------------------------
# overload machinery sharded
# ---------------------------------------------------------------------------

class TestShardedPreemption:
    def test_preempt_resume_sharded(self, models):
        """A low-priority victim preempted mid-decode on a sharded paged
        engine resumes to its full bitwise greedy output with zero new
        compile keys — the replicated host metadata (allocator, prefix
        cache, scheduler) drives all shards through the episode."""
        eng = Engine(_clone(models["gpt"]), mesh=serving_mesh(2),
                     max_preemptions=2, priority_aging_s=30.0,
                     **PAGED_KW)
        eng.warmup()
        warm = eng.metrics.compile_misses
        rs = np.random.RandomState(5)
        p1, p2 = (rs.randint(0, 128, (L,)).tolist() for L in (5, 6))
        a1 = eng.add_request(p1, max_new_tokens=8, priority="low")
        a2 = eng.add_request(p2, max_new_tokens=8, priority="low")
        eng.step()
        eng.step()
        assert a1.state == a2.state == "running"
        hi = eng.add_request(rs.randint(0, 128, (4,)).tolist(),
                             max_new_tokens=4, priority="high")
        eng.run()
        assert a2.preempted and a2.preemptions == 1
        assert a1.finished and a2.finished and hi.finished
        # bitwise: every output (the resumed victim's included) IS the
        # uninterrupted no-cache greedy chain
        for p, r in ((p1, a1), (p2, a2)):
            _assert_greedy_chain(models["gpt"], p, r.output_ids)
        assert eng.metrics.compile_misses == warm
        assert eng.health()["kv_block_invariants"] == "ok"


# ---------------------------------------------------------------------------
# durability sharded
# ---------------------------------------------------------------------------

class TestShardedRecovery:
    def test_recovery_bitwise_same_mesh_shape(self, models, baseline,
                                              tmp_path):
        """Crash a sharded engine mid-decode; a fresh engine on a mesh
        of the SAME SHAPE replays every pending request to the bitwise
        single-chip greedy output."""
        j = RequestJournal(str(tmp_path))
        e1 = Engine(_clone(models["gpt"]), journal=j,
                    mesh=serving_mesh(2), **ENGINE_KW)
        e1.warmup()
        reqs = [e1.add_request(p, max_new_tokens=MAX_NEW)
                for p in PROMPTS]
        for _ in range(3):               # mid-decode "crash": abandon
            e1.step()
        assert any(r.output_ids for r in reqs)

        j2 = RequestJournal(str(tmp_path))
        pending = j2.pending()
        assert len(pending) == 3
        # admissions journaled the mesh shape the work was sharded on
        assert all(rec.get("mesh_shape") == "model=2"
                   for rec in pending.values())
        e2 = Engine(_clone(models["gpt"]), journal=j2,
                    mesh=serving_mesh(2), **ENGINE_KW)
        e2.warmup()
        warm = e2.metrics.compile_misses
        info = e2.recover()
        assert info["replayed"] == 3 and not info["invalid"]
        e2.run()
        got = {tuple(r.prompt_ids.tolist()): r.output_ids
               for r in info["requests"]}
        want = baseline("gpt")
        assert all(got[tuple(p)] == o for p, o in zip(PROMPTS, want))
        assert e2.metrics.compile_misses == warm

    def test_strict_recovery_rejects_mesh_shape_mismatch(self, models,
                                                         tmp_path):
        """``recover(cross_mesh=False)`` keeps the strict shape
        contract: pending work journaled on a model=2 mesh fails
        finally on an engine of a different shape instead of replaying.
        (The DEFAULT since degraded-mode serving is cross-mesh replay —
        tests/test_degraded_serving.py proves it bitwise both
        directions; strict mode remains for operators who want a shape
        mismatch to be loud.)"""
        j = RequestJournal(str(tmp_path))
        e1 = Engine(_clone(models["gpt"]), journal=j,
                    mesh=serving_mesh(2), **ENGINE_KW)
        e1.warmup()
        e1.add_request(PROMPTS[0], max_new_tokens=MAX_NEW)
        e1.step()

        j2 = RequestJournal(str(tmp_path))
        assert len(j2.pending()) == 1
        e2 = Engine(_clone(models["gpt"]), journal=j2, **ENGINE_KW)
        info = e2.recover(cross_mesh=False)   # shape None != model=2
        assert info["replayed"] == 0 and len(info["invalid"]) == 1
        assert info["cross_mesh"] == 0
        # the rejection is durable: a third scan sees no pending work
        j3 = RequestJournal(str(tmp_path))
        assert not j3.pending()
        # strict refusal writes NO mesh_reshard record
        assert j3.mesh_reshards == 0


# ---------------------------------------------------------------------------
# fleet shard groups
# ---------------------------------------------------------------------------

class TestShardGroups:
    def test_hot_swap_rolls_groups_with_flat_misses(self, models):
        """Two shard groups (2 chips each, disjoint) serve; a rolling
        update_weights drains and swaps one GROUP at a time with a flat
        compile-miss counter on every shard group and the fleet healthy
        throughout."""
        fleet = Fleet(_clone(models["gpt"]), num_replicas=2,
                      shards_per_group=2, **PAGED_KW)
        fleet.warmup()
        rs = np.random.RandomState(11)
        reqs = [fleet.submit(rs.randint(0, 128, (L,)).tolist(),
                             max_new_tokens=4)
                for L in (5, 9, 12, 4)]
        fleet.run()
        assert all(r.state == "finished" for r in reqs)
        rows = fleet.metrics.replicas_cb()
        assert [r["mesh_shape"] for r in rows] == ["model=2", "model=2"]
        # the groups really are disjoint device slices
        d0 = set(fleet._group_meshes[0].devices.flat)
        d1 = set(fleet._group_meshes[1].devices.flat)
        assert d0.isdisjoint(d1)
        misses0 = {r["name"]: r["compile_misses"] for r in rows}

        paddle.seed(42)
        new = GPTForCausalLM(gpt_tiny())
        roll = fleet.update_weights(new.state_dict(),
                                    max_drain_steps=2000)
        assert roll["model_version"] == 1
        rows = fleet.metrics.replicas_cb()
        assert {r["name"]: r["compile_misses"] for r in rows} == misses0
        # post-roll traffic serves the NEW weights bitwise
        p = rs.randint(0, 128, (7,)).tolist()
        fr = fleet.submit(p, max_new_tokens=4)
        fleet.run()
        assert fr.state == "finished"
        new.eval()
        _assert_greedy_chain(new, p, fr.output_ids)
        fleet.shutdown()

    def test_shard_group_validation(self):
        with pytest.raises(ValueError, match="shards_per_group"):
            Fleet(gpt_tiny(), num_replicas=2, shards_per_group=0)
        with pytest.raises(ValueError, match="devices"):
            Fleet(gpt_tiny(), num_replicas=8, shards_per_group=2)
        with pytest.raises(ValueError, match="fleet-managed"):
            Fleet(gpt_tiny(), num_replicas=1, mesh=serving_mesh(2))


# ---------------------------------------------------------------------------
# plumbing validation (no compiles)
# ---------------------------------------------------------------------------

class TestShardingPlumbing:
    def test_serving_mesh_validation(self):
        with pytest.raises(ValueError, match=">= 1"):
            serving_mesh(0)
        with pytest.raises(ValueError, match="devices"):
            serving_mesh(1024)
        m = serving_mesh(2)
        assert mesh_shape_key(m) == "model=2"
        assert mesh_shape_key(None) is None

    def test_kv_head_divisibility_guard(self):
        """A mesh wider than the kv-head count must be rejected up
        front: splitting a GQA group across shards would put a head's
        K/V on a different chip than its queries."""
        with pytest.raises(ValueError, match="kv_heads"):
            ServingShard(serving_mesh(4), kv_heads=2, num_heads=4)
        # divisible: fine (llama_tiny on 2 shards)
        ServingShard(serving_mesh(2), kv_heads=2, num_heads=4)

    def test_mesh_needs_model_axis(self):
        from paddle_tpu.distributed import mesh as mesh_mod
        import jax

        m = mesh_mod.build_mesh({"data": 2}, jax.devices()[:2])
        with pytest.raises(ValueError, match="model"):
            ServingShard(m, kv_heads=4, num_heads=4)

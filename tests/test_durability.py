"""Durable serving (ISSUE 14): request journal, crash-consistent
recovery, and zero-downtime rolling weight hot-swap.

Three layers of proof, mirroring the journal's own contract:

- **Journal mechanics** run host-only (milliseconds): CRC-framed
  round-trip, torn-final-record truncation vs interior-corruption
  refusal, segment rotation with fully-terminal-prefix compaction, and
  the prefix-cache version epoch's cross-epoch unhittability.
- **In-process crash simulation** (compiled, cheap): an engine with a
  journal is abandoned mid-flight, a fresh engine recovers from a
  re-scanned journal — every journaled request terminal exactly once,
  greedy AND seeded outputs bitwise identical to an uninterrupted run
  on the same weights, zero steady-state compile misses, metrics
  banked monotone, tracer chain valid with the cross-process recovery
  flow rendered in the Perfetto export.
- **SIGKILL subprocess chaos drill**: a child process journals live
  traffic and SIGKILLs itself mid-decode (no atexit, no flush
  courtesy); a second process recovers and proves the same bar.  The
  rolling hot-swap drill serves live traffic across
  ``Fleet.update_weights`` with zero failed requests and zero new
  compile keys, plus the pinned negative test that a prompt prefilled
  under version N cannot prefix-hit version N+1 blocks.
"""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.obs import chrome_trace
from paddle_tpu.obs.crashdump import persist_crash_artifacts
from paddle_tpu.serving import (
    BlockAllocator, Engine, Fleet, JournalCorrupt, PrefixCache,
    RequestJournal, RequestTracer, SamplingParams, validate_trace,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def new_weights(model):
    """A second, different weight set with identical shapes (the
    hot-swap payload)."""
    paddle.seed(7)
    m2 = GPTForCausalLM(gpt_tiny())
    return m2.state_dict()


def _mk_engine(model, tmp=None, journal=None, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("min_bucket", 8)
    kw.setdefault("block_size", 8)
    if journal is None and tmp is not None:
        journal = RequestJournal(str(tmp))
    return Engine(model, journal=journal, **kw)


def _admit_args(jid, **over):
    base = dict(prompt_ids=[1, 2, 3],
                sampling={"temperature": 0.0, "top_k": 0, "top_p": 1.0,
                          "seed": None},
                seed_effective=7919, priority=1, deadline_s=None,
                max_new_tokens=4, eos_token_id=None, engine="e0",
                model_version=0)
    base.update(over)
    return jid, base


# ---------------------------------------------------------------------------
# journal mechanics (host-only)
# ---------------------------------------------------------------------------

class TestJournalRoundTrip:
    def test_records_survive_reopen(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.record_tokens("e0", 1, {jid: 5})
        j.record_tokens("e0", 2, {jid: 9})
        jid2, kwargs2 = _admit_args("e0:b1:r1", prompt_ids=[4, 5])
        j.record_admission(jid2, **kwargs2)
        j.record_end(jid, "finished", n_tokens=2, engine="e0")
        j.close()

        j2 = RequestJournal(str(tmp_path))
        assert list(j2.pending().keys()) == [jid2]
        assert j2.pending()[jid2]["prompt_ids"] == [4, 5]
        assert j2.outputs(jid) == [5, 9]
        assert j2.outcomes() == {"finished": 1}
        a = j2.audit()
        assert a["admitted"] == 2 and a["finals"] == 1
        assert a["duplicate_terminals"] == 0 and a["torn_records"] == 0
        # a fresh instance never appends to an old (possibly-torn)
        # segment, and its boot marker advances past every old segment
        assert j2.boot > j.boot

    def test_restart_supersedes_tokens(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.record_tokens("e0", 1, {jid: 5})
        j.record_restart(jid, "preempt")
        j.record_tokens("e0", 9, {jid: 8})
        assert j.outputs(jid) == [8]

    def test_duplicate_final_is_audited(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.record_end(jid, "finished")
        j.record_end(jid, "finished")
        assert j.audit()["duplicate_terminals"] == 1

    def test_bad_config_rejected(self, tmp_path):
        with pytest.raises(ValueError):
            RequestJournal(str(tmp_path), fsync="sometimes")
        with pytest.raises(ValueError):
            RequestJournal(str(tmp_path), segment_records=0)


class TestTornRecordRecovery:
    def _seg_paths(self, tmp_path):
        return sorted(p for p in os.listdir(tmp_path)
                      if p.endswith(".jrnl"))

    def test_torn_final_record_truncated(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.record_end(jid, "finished")
        jid2, kwargs2 = _admit_args("e0:b1:r1")
        j.record_admission(jid2, **kwargs2)
        j.close()
        seg = os.path.join(tmp_path, self._seg_paths(tmp_path)[-1])
        with open(seg, "ab") as f:        # a crash mid-append: no newline
            f.write(b'0badc0de {"kind":"end","jid":"e0:b1:r1","fin')
        j2 = RequestJournal(str(tmp_path))
        assert j2.torn_records == 1
        # the torn final end never committed: r1 is still pending
        assert list(j2.pending().keys()) == [jid2]
        assert j2.audit()["duplicate_terminals"] == 0

    def test_torn_crc_with_newline_truncated(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.close()
        seg = os.path.join(tmp_path, self._seg_paths(tmp_path)[-1])
        with open(seg, "ab") as f:
            f.write(b'deadbeef {"kind":"end","jid":"e0:b1:r0"}\n')
        j2 = RequestJournal(str(tmp_path))
        assert j2.torn_records == 1
        assert list(j2.pending().keys()) == [jid]

    def test_torn_tail_truncated_on_disk_double_reopen(self, tmp_path):
        """The tear is removed FROM THE FILE at first reopen: once the
        recovering process opens a fresh segment, the torn one is no
        longer last, and an un-truncated tear would read as interior
        corruption on the NEXT crash's reopen."""
        j = RequestJournal(str(tmp_path))
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)
        j.close()
        seg = os.path.join(tmp_path, self._seg_paths(tmp_path)[-1])
        with open(seg, "ab") as f:
            f.write(b'0badc0de {"kind":"end","jid":"e0:b1:r0"')
        j2 = RequestJournal(str(tmp_path))
        assert j2.torn_records == 1
        j2.record_tokens("e0", 1, {jid: 5})       # a later segment exists
        j2.close()
        j3 = RequestJournal(str(tmp_path))        # second crash's reopen
        assert j3.torn_records == 0               # tear gone from disk
        assert list(j3.pending().keys()) == [jid]
        assert j3.outputs(jid) == [5]

    def test_interior_corruption_refused(self, tmp_path):
        j = RequestJournal(str(tmp_path))
        for i in range(3):
            jid, kwargs = _admit_args(f"e0:b1:r{i}")
            j.record_admission(jid, **kwargs)
        j.close()
        seg = os.path.join(tmp_path, self._seg_paths(tmp_path)[-1])
        with open(seg, "rb") as f:
            lines = f.read().splitlines(keepends=True)
        lines[1] = b'00000000 {"kind":"zap"}\n'   # interior CRC break
        with open(seg, "wb") as f:
            f.writelines(lines)
        with pytest.raises(JournalCorrupt):
            RequestJournal(str(tmp_path))


class TestSegmentsAndCompaction:
    def test_rotation_compacts_fully_terminal_prefix(self, tmp_path):
        j = RequestJournal(str(tmp_path), segment_records=4)
        # r0/r1 admitted AND finished inside the early segments
        for i in range(2):
            jid, kwargs = _admit_args(f"e0:b1:r{i}")
            j.record_admission(jid, **kwargs)
            j.record_end(jid, "finished")
        # r2 stays pending: its segments (and everything after) survive
        jid2, kwargs2 = _admit_args("e0:b1:r2")
        j.record_admission(jid2, **kwargs2)
        for step in range(12):            # force several rotations
            j.record_tokens("e0", step, {jid2: step})
        assert j.compacted_segments >= 1
        j.close()
        j2 = RequestJournal(str(tmp_path))
        # compaction never loses replay state: r2 still pending with
        # its full token tail, r0/r1 never resurrected as pending — and
        # their OUTCOMES survive via the cumulative compacted record,
        # so a recovery's banked counters stay monotone even after the
        # segments holding the final ends were deleted
        assert list(j2.pending().keys()) == [jid2]
        assert j2.outputs(jid2) == list(range(12))
        assert j2.outcomes() == {"finished": 2}
        a = j2.audit()
        assert a["admitted"] == 3 and a["finals"] == 2

    def test_straddling_request_compacts_with_its_whole_prefix(
            self, tmp_path):
        """A request whose records straddle a rotation boundary drops
        together with the whole prefix containing them — containment is
        judged against the candidate prefix's end, not each segment's
        own index (a per-segment check would block compaction forever
        under steady traffic)."""
        j = RequestJournal(str(tmp_path), segment_records=2)
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)        # seg1: admit, tok
        j.record_tokens("e0", 0, {jid: 1})       # (rotates)
        j.record_tokens("e0", 1, {jid: 2})       # seg2: tok, end(r0)
        j.record_end(jid, "finished")            # (rotates + compacts)
        assert j.compacted_segments == 2         # [seg1, seg2] dropped
        jid2, kwargs2 = _admit_args("e0:b1:r1")
        j.record_admission(jid2, **kwargs2)      # pending survivor
        # compaction pruned r0's per-jid replay state (bounded memory)
        # but the LIVE audit totals still count it via the aggregates
        assert jid2 in j._admissions and jid not in j._admissions
        a = j.audit()
        assert a["admitted"] == 2 and a["finals"] == 1
        assert a["duplicate_terminals"] == 0
        j.close()
        j2 = RequestJournal(str(tmp_path))
        assert list(j2.pending().keys()) == [jid2]

    def test_pending_request_blocks_compaction(self, tmp_path):
        j = RequestJournal(str(tmp_path), segment_records=2)
        jid, kwargs = _admit_args("e0:b1:r0")
        j.record_admission(jid, **kwargs)     # pending forever
        for step in range(8):
            j.record_tokens("e0", step, {jid: step})
        assert j.compacted_segments == 0
        assert RequestJournal(str(tmp_path)).outputs(jid) == \
            list(range(8))


class TestPrefixEpoch:
    def test_cross_epoch_blocks_never_hit(self):
        alloc = BlockAllocator(num_blocks=16)
        pc = PrefixCache(alloc, block_size=4)
        prompt = list(range(12))
        blocks = alloc.alloc(2)
        pc.register(prompt, blocks)
        hit, ids = pc.lookup(prompt)
        assert hit == 8 and ids == blocks
        epoch = pc.bump_epoch()
        assert epoch == 1
        # version-N blocks are unreachable under version N+1: disjoint
        # hash domains, not just an emptied table
        assert pc.probe(prompt) == 0
        assert pc.lookup(prompt) == (0, [])
        # idle entries were dropped, their blocks back in the pool
        assert len(pc) == 0
        # re-registering under the NEW epoch hits again
        blocks2 = alloc.alloc(2)
        pc.register(prompt, blocks2)
        assert pc.lookup(prompt)[0] == 8
        assert pc.stats()["epoch"] == 1

    def test_pinned_entries_survive_bump_unhittable(self):
        alloc = BlockAllocator(num_blocks=16)
        pc = PrefixCache(alloc, block_size=4)
        prompt = list(range(8))
        blocks = alloc.alloc(1)
        pc.register(prompt, blocks)
        alloc.ref(blocks[0])              # a live slot still holds it
        pc.bump_epoch()
        # pinned: the cache's ref remains (freeing it would corrupt the
        # live slot), but the entry is unreachable either way
        assert pc.probe(prompt) == 0
        assert alloc.refcount(blocks[0]) >= 1


# ---------------------------------------------------------------------------
# in-process crash simulation (compiled)
# ---------------------------------------------------------------------------

class TestEngineRecovery:
    def test_abandon_and_recover_bitwise(self, model, tmp_path):
        j = RequestJournal(str(tmp_path))
        tracer = RequestTracer()
        eng = _mk_engine(model, journal=j)
        eng.warmup()
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, 128, (L,)).tolist() for L in (5, 9, 12)]
        r_greedy0 = eng.add_request(prompts[0], max_new_tokens=6)
        r_seeded = eng.add_request(
            prompts[1], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.8, top_k=8, seed=123))
        r_unseeded = eng.add_request(
            prompts[2], max_new_tokens=6,
            sampling=SamplingParams(temperature=0.8))
        for _ in range(3):                # mid-decode "crash": abandon
            eng.step()
        assert any(r.output_ids
                   for r in (r_greedy0, r_seeded, r_unseeded))

        j2 = RequestJournal(str(tmp_path))
        assert len(j2.pending()) == 3
        eng2 = _mk_engine(model, journal=j2, tracer=tracer)
        eng2.warmup()
        misses0 = eng2.metrics.compile_misses
        info = eng2.recover()
        assert info["replayed"] == 3
        assert all(r.recovered for r in info["requests"])
        # journal ids survive the crash — the exactly-once audit spans it
        assert [r.journal_id for r in info["requests"]] == \
            list(j2.pending().keys())
        eng2.run()
        assert all(r.state == "finished" for r in info["requests"])
        # zero steady-state compile misses through the whole recovery
        assert eng2.metrics.compile_misses == misses0
        a = j2.audit()
        assert a["pending"] == 0 and a["duplicate_terminals"] == 0

        # bitwise vs an uninterrupted run on the same weights: greedy,
        # seeded, AND unseeded (the journaled effective seed replays
        # the exact stream the crashed attempt was drawing)
        rec = info["requests"]
        ref = [
            eng2.add_request(prompts[0], max_new_tokens=6),
            eng2.add_request(prompts[1], max_new_tokens=6,
                             sampling=SamplingParams(temperature=0.8,
                                                     top_k=8, seed=123)),
            # the unseeded request's reference replays the journaled
            # effective seed (recovery resolved it onto the handle)
            eng2.add_request(prompts[2], max_new_tokens=6,
                             sampling=SamplingParams(
                                 temperature=0.8,
                                 seed=rec[2].sampling.seed)),
        ]
        eng2.run()
        assert [r.output_ids for r in ref] == \
            [r.output_ids for r in rec]

        # the journal's own token trail equals the delivered streams
        for r in rec:
            assert j2.outputs(r.journal_id) == r.output_ids

        # tracer: chain valid, recovered events present, Perfetto
        # renders the wall-anchored cross-process flow
        assert validate_trace(tracer) == []
        recov = [e for e in tracer.events if e["kind"] == "recovered"]
        assert len(recov) == 3
        assert all(e.get("origin_wall") for e in recov)
        ct = chrome_trace(tracer)
        names = [e.get("name") for e in ct["traceEvents"]]
        assert "pre_crash_admission" in names
        flows = [e for e in ct["traceEvents"]
                 if e.get("cat") == "link" and e.get("name") == "recovered"]
        assert any(e["ph"] == "s" for e in flows)
        assert any(e["ph"] == "f" for e in flows)

    def test_metrics_banked_monotone(self, model, tmp_path):
        j = RequestJournal(str(tmp_path))
        eng = _mk_engine(model, journal=j)
        eng.warmup()
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
        assert eng.metrics.requests_completed == 2
        eng.add_request([7, 8, 9], max_new_tokens=4)
        eng.step()                        # in flight at the "crash"

        j2 = RequestJournal(str(tmp_path))
        eng2 = _mk_engine(model, journal=j2)
        eng2.warmup()
        info = eng2.recover()
        assert info["outcomes"] == {"finished": 2}
        st = eng2.stats()
        # pre-crash completions banked: the counter continues, not resets
        assert st["requests"]["completed"] == 2
        assert st["durability"]["banked"] == {"finished": 2}
        assert st["durability"]["recovered"] == 1
        eng2.run()
        assert eng2.stats()["requests"]["completed"] == 3

    def test_recovered_replays_are_never_shed(self, model, tmp_path,
                                              monkeypatch):
        """SLO shedding must not drop a replay: the work was accepted
        once already, before the crash.  Even with the wait estimator
        forced sky-high (a warmed engine under a replay backlog),
        recovery admits every journaled request — only FRESH traffic
        sheds."""
        j = RequestJournal(str(tmp_path))
        eng = _mk_engine(model, journal=j)
        eng.warmup()
        for i in range(4):
            eng.add_request([1 + i, 2, 3], max_new_tokens=6,
                            deadline_s=30.0)
        eng.step()                        # in flight at the "crash"

        j2 = RequestJournal(str(tmp_path))
        eng2 = _mk_engine(model, journal=j2)
        eng2.warmup()
        # estimator says every deadline is doomed: fresh traffic sheds,
        # recovered replays must not
        monkeypatch.setattr(type(eng2), "estimate_queue_wait_s",
                            lambda self, priority=1: 1e6)
        from paddle_tpu.serving import ShedReject
        with pytest.raises(ShedReject):
            eng2.add_request([7, 7, 7], max_new_tokens=4,
                             deadline_s=30.0)
        info = eng2.recover()
        assert info["replayed"] == 4      # nothing shed, nothing lost
        monkeypatch.undo()
        eng2.run()
        assert all(r.state == "finished" for r in info["requests"])
        assert j2.audit()["duplicate_terminals"] == 0

    def test_invalid_replay_isolated_not_wedging(self, model, tmp_path):
        """A replay the restarted engine cannot validate (the restart
        shrank max_seq) fails THAT request with a final journal end —
        the rest still replay, and a later recover() is not wedged on
        the same jid forever."""
        j = RequestJournal(str(tmp_path))
        eng = _mk_engine(model, journal=j, max_seq=64)
        eng.warmup()
        big = eng.add_request(list(range(40)), max_new_tokens=4)
        ok = eng.add_request([1, 2, 3], max_new_tokens=4)
        eng.step()                        # both in flight at the "crash"

        j2 = RequestJournal(str(tmp_path))
        eng2 = _mk_engine(model, journal=j2, max_seq=32)
        eng2.warmup()
        info = eng2.recover()
        assert info["replayed"] == 1 and len(info["invalid"]) == 1
        eng2.run()
        a = j2.audit()
        assert a["pending"] == 0 and a["duplicate_terminals"] == 0
        assert info["requests"][0].state == "finished"
        # idempotent: a second recover finds nothing left to replay
        assert eng2.recover()["replayed"] == 0
        del big, ok

    def test_recover_requires_idle_engine(self, model, tmp_path):
        j = RequestJournal(str(tmp_path))
        eng = _mk_engine(model, journal=j)
        eng.warmup()
        eng.add_request([1, 2], max_new_tokens=2)
        with pytest.raises(RuntimeError, match="before serving"):
            eng.recover()
        with pytest.raises(ValueError, match="RequestJournal"):
            _mk_engine(model).recover()

    def test_recover_journal_mismatch_refused(self, model, tmp_path):
        """Replaying journal B while recording into journal A would
        leave B's pending set non-converging (a later recover from B
        duplicates completed work)."""
        ja = RequestJournal(str(tmp_path / "a"))
        jb = RequestJournal(str(tmp_path / "b"))
        eng = _mk_engine(model, journal=ja)
        with pytest.raises(ValueError, match="does not match"):
            eng.recover(jb)

    def test_journal_write_failure_rejects_cleanly(self, model,
                                                   tmp_path):
        """A failing admission write (disk full, closed file) must not
        leave the engine serving a request its caller was told failed:
        the WAL commits BEFORE the enqueue, and on failure the handle
        is rejected with nothing half-admitted."""
        j = RequestJournal(str(tmp_path))
        eng = _mk_engine(model, journal=j)
        j._seg.close()                    # simulate the storage failing
        with pytest.raises(ValueError) as ei:
            eng.add_request([1, 2, 3], max_new_tokens=2)
        assert not eng.queue              # nothing enqueued
        req = ei.value.request
        assert req.state == "rejected"
        assert "journal admission write failed" in req.error
        assert req.journal_id is None     # nothing durable to audit


class TestEngineHotSwap:
    def test_update_requires_idle(self, model, new_weights):
        eng = _mk_engine(model)
        eng.warmup()
        eng.add_request([1, 2, 3], max_new_tokens=4)
        with pytest.raises(RuntimeError, match="drain"):
            eng.update_weights(new_weights)

    def test_partial_state_dict_refused(self, model, new_weights):
        eng = _mk_engine(model)
        partial = dict(list(new_weights.items())[:3])
        with pytest.raises(ValueError, match="does not cover"):
            eng.update_weights(partial)

    def test_swap_in_place_zero_new_keys(self, model, new_weights,
                                         tmp_path):
        paddle.seed(0)
        own = GPTForCausalLM(gpt_tiny())   # private copy: don't mutate
        own.set_state_dict(model.state_dict())
        own.eval()
        j = RequestJournal(str(tmp_path))
        eng = Engine(own, num_slots=2, max_seq=32, min_bucket=8,
                     block_size=8, journal=j)
        eng.warmup()
        prompt = list(range(20))
        eng.generate([prompt], max_new_tokens=5)
        # second serve prefix-hits the registered v0 blocks
        r2 = eng.add_request(prompt, max_new_tokens=5)
        eng.run()
        assert eng.prefix_cache.hit_tokens_total > 0
        assert r2.model_version == 0
        misses = eng.metrics.compile_misses
        hit_before = eng.prefix_cache.hit_tokens_total

        v = eng.update_weights(new_weights)
        assert v == 1 and eng.model_version == 1
        assert eng.prefix_cache.epoch == 1

        # negative test: the same prompt CANNOT prefix-hit the v0
        # blocks — the hit counters do not move on the v1 admission
        r3 = eng.add_request(prompt, max_new_tokens=5)
        eng.run()
        assert eng.prefix_cache.hit_tokens_total == hit_before
        assert r3.model_version == 1
        # the swap reused every warmed executable: zero new keys
        assert eng.metrics.compile_misses == misses
        # the new weights are REALLY in the serving buffers (written
        # through in place, same tensor objects the executables lifted)
        want = np.asarray(new_weights[next(iter(new_weights))].numpy())
        got = own.state_dict()[next(iter(new_weights))].numpy()
        np.testing.assert_array_equal(got, want)
        st = eng.stats()["durability"]
        assert st["weight_swaps"] == 1 and st["model_version"] == 1
        assert st["journal"]["records_written"] > 0


# ---------------------------------------------------------------------------
# fleet: rolling hot-swap under live traffic + crash recovery
# ---------------------------------------------------------------------------

class TestFleetDurability:
    def test_rolling_update_under_live_traffic(self, model, new_weights,
                                               tmp_path):
        j = RequestJournal(str(tmp_path))
        fleet = Fleet(model, num_replicas=2, num_slots=2, max_seq=32,
                      min_bucket=8, block_size=8,
                      journal=j)
        fleet.warmup()
        assert fleet.weights_isolated
        rs = np.random.RandomState(11)
        prompts = [rs.randint(0, 128, (L,)).tolist()
                   for L in (5, 9, 12, 7)]
        live = [fleet.submit(p, max_new_tokens=8) for p in prompts]
        for _ in range(2):
            fleet.step()                  # tokens flowing on both replicas
        misses = {rep.engine.name: rep.engine.metrics.compile_misses
                  for rep in fleet.replicas}

        res = fleet.update_weights(new_weights, max_drain_steps=500)
        assert res["model_version"] == 1
        assert res["replicas_updated"] == 2

        # zero failed/lost requests across the roll; in-flight work
        # finished under the weights that admitted it (v0)
        assert all(r.state == "finished" for r in live)
        assert all(r.model_version == 0 for r in live)

        post = [fleet.submit(p, max_new_tokens=6) for p in prompts[:2]]
        fleet.run()
        assert all(r.state == "finished" for r in post)
        assert all(r.model_version == 1 for r in post)

        # zero new executable-cache keys on every replica
        for rep in fleet.replicas:
            assert rep.engine.metrics.compile_misses == \
                misses[rep.engine.name]
            assert rep.engine.prefix_cache.epoch == 1
            assert rep.engine.model_version == 1
        st = fleet.stats()
        assert st["requests"]["failed"] == 0
        assert st["requests"]["duplicate_terminals"] == 0
        assert st["durability"]["weight_rolls"] == 1
        assert st["durability"]["model_version"] == 1
        assert j.audit()["duplicate_terminals"] == 0
        fleet.shutdown(timeout_s=0.0)

    def test_weight_isolation_replicas_own_buffers(self, model):
        fleet = Fleet(model, num_replicas=2, num_slots=2, max_seq=32,
                      min_bucket=8, block_size=8)
        p0 = fleet.replicas[0].engine.model.parameters()[0]
        p1 = fleet.replicas[1].engine.model.parameters()[0]
        assert p0 is not p1               # isolated buffers...
        np.testing.assert_array_equal(p0.numpy(), p1.numpy())  # ...same
        assert fleet.replicas[0].engine.model is not model     # weights

    def test_fleet_recover_refuses_live_fleet(self, model, tmp_path):
        """recover() on a fleet with in-flight work would replay every
        live request under its own journal id — a guaranteed duplicate
        terminal.  Refused, like the engine-level guard."""
        j = RequestJournal(str(tmp_path))
        fleet = Fleet(model, num_replicas=1, num_slots=2, max_seq=32,
                      min_bucket=8, block_size=8, journal=j)
        fleet.warmup()
        fleet.submit([1, 2, 3], max_new_tokens=6)
        fleet.step()
        with pytest.raises(RuntimeError, match="before serving"):
            fleet.recover()
        fleet.shutdown(timeout_s=0.0)

    def test_fleet_recover_exactly_once(self, model, tmp_path):
        j = RequestJournal(str(tmp_path))
        fleet = Fleet(model, num_replicas=1, num_slots=2, max_seq=32,
                      min_bucket=8, block_size=8, journal=j)
        fleet.warmup()
        done = fleet.submit([1, 2, 3], max_new_tokens=2)
        fleet.run()
        assert done.state == "finished"
        pend = [fleet.submit([4, 5, 6, 7], max_new_tokens=6),
                fleet.submit([8, 9], max_new_tokens=6)]
        fleet.step()                      # in flight at the "crash"
        assert any(not r.done for r in pend)

        j2 = RequestJournal(str(tmp_path))
        fleet2 = Fleet(model, num_replicas=1, num_slots=2, max_seq=32,
                       min_bucket=8, block_size=8, journal=j2)
        fleet2.warmup()
        info = fleet2.recover()
        assert info["replayed"] == 2
        assert info["outcomes"] == {"finished": 1}
        assert all(r.recovered for r in info["requests"])
        fleet2.run()
        assert all(r.state == "finished" for r in info["requests"])
        a = j2.audit()
        assert a["pending"] == 0 and a["duplicate_terminals"] == 0
        st = fleet2.stats()
        # banked: completed counts the pre-crash finish too
        assert st["requests"]["completed"] == 3
        assert st["requests"]["duplicate_terminals"] == 0
        assert st["durability"]["crash_recoveries"] == 1
        assert st["durability"]["recovered"] == 2
        fleet2.shutdown(timeout_s=0.0)


# ---------------------------------------------------------------------------
# crash artifact persistence (satellite: the dump outlives the process)
# ---------------------------------------------------------------------------

class TestCrashDump:
    def test_persists_flight_and_trace(self, tmp_path, monkeypatch):
        from paddle_tpu.obs.flight import FlightRecorder

        monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
        rec = FlightRecorder(8, name="crash-unit")
        rec.record(step=1, running=2)
        tracer = RequestTracer()
        tracer.on_eject("r0", "unit")
        dumps_before = list(rec.dumps)
        p = persist_crash_artifacts("unit-test crash")
        assert p is not None and os.path.exists(p)
        payload = json.load(open(p))
        assert payload["reason"] == "unit-test crash"
        ring = payload["flight_rings"]["crash-unit"][-1]
        assert ring["reason"] == "crash: unit-test crash"
        assert any(e.get("step") == 1 for e in ring["events"])
        # persisting is a READ: no dump was banked on the live recorder
        # (consumers assert on dumps[-1] identity — see test_sentry)
        assert rec.dumps == dumps_before
        assert any(ev["kind"] == "eject"
                   for tr in payload["traces"] for ev in tr["events"])

    def test_no_destination_is_noop(self, monkeypatch):
        from paddle_tpu.obs import crashdump

        monkeypatch.delenv("PADDLE_TPU_TRACE_DIR", raising=False)
        monkeypatch.setattr(crashdump, "_JOURNAL_DIRS", [])
        assert crashdump.persist_crash_artifacts("nowhere") is None

    def test_journal_dir_fallback(self, tmp_path, monkeypatch):
        from paddle_tpu.obs import crashdump

        monkeypatch.delenv("PADDLE_TPU_TRACE_DIR", raising=False)
        RequestJournal(str(tmp_path / "j"))
        p = persist_crash_artifacts("fallback")
        assert p is not None
        assert os.path.dirname(p) == str(tmp_path / "j" / "crash")


# ---------------------------------------------------------------------------
# SIGKILL subprocess chaos drill (the acceptance bar)
# ---------------------------------------------------------------------------

_CHILD_SERVE = r"""
import os, signal, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import Engine, RequestJournal, SamplingParams

paddle.seed(0)
eng = Engine(GPTForCausalLM(gpt_tiny()), num_slots=2, max_seq=32,
             min_bucket=8, block_size=8,
             journal=RequestJournal(sys.argv[1]))
eng.warmup()
rs = np.random.RandomState(5)
prompts = [rs.randint(0, 128, (L,)).tolist() for L in (6, 11, 14)]
eng.add_request(prompts[0], max_new_tokens=8)
eng.add_request(prompts[1], max_new_tokens=8,
                sampling=SamplingParams(temperature=0.7, top_k=8,
                                        seed=99))
eng.add_request(prompts[2], max_new_tokens=8)
steps = 0
while eng.step():
    steps += 1
    if steps == 3:                  # mid-decode, tokens already streamed
        print("KILLING", flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
raise SystemExit("unreachable: the SIGKILL must land mid-drill")
"""

_CHILD_RECOVER = r"""
import json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import Engine, RequestJournal, SamplingParams

paddle.seed(0)
j = RequestJournal(sys.argv[1])
pend = j.pending()
eng = Engine(GPTForCausalLM(gpt_tiny()), num_slots=2, max_seq=32,
             min_bucket=8, block_size=8, journal=j)
eng.warmup()
misses0 = eng.metrics.compile_misses
info = eng.recover()
eng.run()
rec = info["requests"]

# uninterrupted reference on the SAME process's weights, rebuilt from
# the journaled replay recipes (seed_effective included)
refs = []
for jid, r in zip(pend, rec):
    rec_ad = pend[jid]
    s = dict(rec_ad["sampling"])
    if s.get("seed") is None:
        s["seed"] = rec_ad["seed_effective"]
    refs.append(eng.add_request(rec_ad["prompt_ids"],
                                max_new_tokens=rec_ad["max_new_tokens"],
                                sampling=SamplingParams(**s)))
eng.run()
a = j.audit()
print(json.dumps({
    "replayed": info["replayed"],
    "recovered_flags": [bool(r.recovered) for r in rec],
    "all_finished": all(r.state == "finished" for r in rec),
    "bitwise": [r.output_ids for r in rec] == [r.output_ids for r in refs],
    "steady_misses": eng.metrics.compile_misses - misses0,
    "pending_after": a["pending"],
    "duplicate_terminals": a["duplicate_terminals"],
    "banked": eng.stats()["durability"]["banked"],
}))
"""


class TestSigkillChaosDrill:
    def test_sigkill_mid_decode_recovery(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        jdir = str(tmp_path / "journal")
        r1 = subprocess.run([sys.executable, "-c", _CHILD_SERVE, jdir],
                            cwd=REPO, env=env, capture_output=True,
                            text=True, timeout=300)
        # the child must die BY SIGKILL mid-drill, not exit cleanly
        assert r1.returncode == -signal.SIGKILL, \
            (r1.returncode, r1.stdout[-2000:], r1.stderr[-2000:])
        assert "KILLING" in r1.stdout

        r2 = subprocess.run([sys.executable, "-c", _CHILD_RECOVER, jdir],
                            cwd=REPO, env=env, capture_output=True,
                            text=True, timeout=300)
        assert r2.returncode == 0, (r2.stdout[-2000:],
                                    r2.stderr[-2000:])
        out = json.loads(r2.stdout.strip().splitlines()[-1])
        # every journaled request terminal EXACTLY once across the
        # crash, outputs bitwise identical to an uninterrupted run,
        # zero steady-state compile misses during recovery
        assert out["replayed"] == 3
        assert out["recovered_flags"] == [True, True, True]
        assert out["all_finished"] is True
        assert out["bitwise"] is True
        assert out["steady_misses"] == 0
        assert out["pending_after"] == 0
        assert out["duplicate_terminals"] == 0


_CHILD_WATCHDOG = r"""
import sys, time
from paddle_tpu.distributed.fault_tolerance.watchdog import StepWatchdog
from paddle_tpu.obs.flight import FlightRecorder

rec = FlightRecorder(8, name="wd-crash")
rec.record(step=1, running=1)
wd = StepWatchdog(0.2, hard_exit=True, startup_factor=1.0)
wd.start()
wd.notify(0)
wd.notify(1)                      # two boundaries: warmed deadline
time.sleep(30)                    # wedge: the watchdog must os._exit
raise SystemExit("unreachable")
"""


class TestWatchdogCrashPersistence:
    def test_hard_exit_persists_artifacts(self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_TRACE_DIR=str(tmp_path))
        r = subprocess.run([sys.executable, "-c", _CHILD_WATCHDOG],
                           cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 101, (r.returncode, r.stderr[-2000:])
        crash = [f for f in os.listdir(tmp_path)
                 if f.startswith("crash-")]
        assert len(crash) == 1, (os.listdir(tmp_path),
                                 r.stderr[-2000:])
        payload = json.load(open(tmp_path / crash[0]))
        assert payload["reason"].startswith("watchdog:")
        assert "wd-crash" in payload["flight_rings"]
        assert payload["flight_rings"]["wd-crash"][-1]["events"]
        assert "crash artifacts persisted" in r.stderr

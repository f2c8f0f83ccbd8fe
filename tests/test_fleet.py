"""Serving fleet supervisor: replica-scoped chaos, prefix-affinity
dispatch, redispatch budget, exactly-once terminals (ISSUE 6).

The acceptance scenario (a scoped fault plan killing 1 of 3 replicas
mid-decode) runs ONCE in the session-scope ``fleet_chaos`` fixture
(tests/conftest.py, shared with test_tracing.py's trace-chain
validation); the assertions ride in separate tests and later tests
reuse the healed fleet, so the file pays for four engine warmups total.
No test here may be marked ``slow`` — tools/collect_gate.py fails CI if
fleet coverage would drop out of tier-1.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fault_tolerance import (
    InjectedFault, ServingFaultPlan,
)
from paddle_tpu.serving import (
    EngineStopped, Fleet, FleetRequest, QueueFull,
)


@pytest.fixture(scope="module")
def gpt(serving_model):
    """The session-shared tiny GPT (tests/conftest.py) — ISSUE 9 moved
    it up so test_tracing.py can validate the SAME chaos run without
    paying for a second fleet."""
    return serving_model


def _full_logits(model, seq):
    with paddle.no_grad():
        out = model(paddle.to_tensor(np.asarray(seq, np.int64)[None]))
    return out.numpy()[0]


def _assert_greedy_chain(model, prompt, out_ids):
    """``out_ids`` must BE the no-cache greedy generation for ``prompt``
    (one causal forward yields every step's reference logits)."""
    L = len(prompt)
    full = list(prompt) + [int(t) for t in out_ids]
    logits = _full_logits(model, full[:-1])
    for i, t in enumerate(out_ids):
        assert int(np.argmax(logits[L - 1 + i])) == int(t), (i, t)


class TestScopedFaultPlan:
    """ISSUE 6 satellite: replica-scoped fault points
    (``serving.r<k>.<point>``) so chaos can target exactly one replica,
    with old unscoped specs keeping their global-call semantics."""

    def test_scoped_spec_parsing(self):
        plan = ServingFaultPlan.from_env(
            {"PADDLE_TPU_FT_SERVING_FAULTS":
             "serving.r1.decode@2x2, serving.prefill@3"})
        assert plan.armed
        # unscoped check never trips a scoped rule
        for _ in range(5):
            plan.check("serving.decode")
        # scoped points validate against the canonical point list
        with pytest.raises(ValueError):
            ServingFaultPlan().add("serving.r1.nope", at_call=1)
        with pytest.raises(ValueError):
            ServingFaultPlan.from_env(
                {"PADDLE_TPU_FT_SERVING_FAULTS": "serving.r1.bogus@1"})

    def test_scoped_views_count_per_replica(self):
        plan = ServingFaultPlan().add("serving.r1.decode", at_call=2)
        v0, v1 = plan.scoped(0), plan.scoped(1)
        # replica 0 sails past call 2 — the rule is scoped to replica 1
        for _ in range(4):
            v0.check("serving.decode")
        v1.check("serving.decode")                  # r1 call #1: clean
        with pytest.raises(InjectedFault, match="serving.r1.decode"):
            v1.check("serving.decode")              # r1 call #2: fires
        assert v0.calls("serving.decode") == 4
        assert v1.calls("serving.decode") == 2
        # both views also advanced the fleet-wide unscoped counter
        assert plan.calls("serving.decode") == 6

    def test_unscoped_rule_fires_on_global_call_order(self):
        """Old specs keep working: an unscoped rule counts calls across
        ALL replicas' scoped views, in arrival order."""
        plan = ServingFaultPlan().add("serving.prefill", at_call=3)
        v0, v1 = plan.scoped(0), plan.scoped(1)
        v0.check("serving.prefill")                 # global #1
        v1.check("serving.prefill")                 # global #2
        with pytest.raises(InjectedFault, match="call #3"):
            v0.check("serving.prefill")             # global #3 fires
        assert plan.calls("serving.prefill") == 3
        assert plan.calls("serving.r0.prefill") == 2


# -- the acceptance scenario: kill 1 of 3 replicas mid-decode --------------
# The scenario itself now runs ONCE per session in tests/conftest.py
# (``fleet_chaos``) with a RequestTracer attached, shared with
# test_tracing.py's chain validation; this module asserts the failover
# semantics on that same run.

MAX_NEW = 4          # kept in lockstep with conftest.fleet_chaos


@pytest.fixture(scope="module")
def chaos(fleet_chaos):
    assert fleet_chaos["max_new"] == MAX_NEW
    return fleet_chaos


class TestFleetChaos:
    """ISSUE 6 acceptance: every accepted request reaches a terminal
    state exactly once, survivors add zero compile misses, and the
    ejected replica is rebuilt and serves again."""

    def test_all_requests_terminal_exactly_once(self, gpt, chaos):
        reqs, terminals = chaos["reqs"], chaos["terminals"]
        assert sorted(terminals) == sorted(r.request_id for r in reqs)
        assert len(terminals) == len(set(terminals))    # once each
        st = chaos["fleet"].stats()
        assert st["requests"]["duplicate_terminals"] == 0
        assert st["requests"]["completed"] == len(reqs)
        assert st["requests"]["failed"] == 0
        # every request finished with the full greedy output — including
        # the replayed ones (replay-from-prompt is deterministic greedy)
        for p, r in zip(chaos["prompts"], reqs):
            assert r.finished and len(r.output_ids) == MAX_NEW
            _assert_greedy_chain(gpt, p, r.output_ids)
        json.dumps(st)

    def test_redispatch_stream_restarts_from_token_zero(self, chaos):
        reqs, streamed = chaos["reqs"], chaos["streamed"]
        moved = [r for r in reqs if r.redispatches > 0]
        assert moved, "the scoped fault must have orphaned requests"
        for r in moved:
            assert r.redispatched and r.redispatches <= 2
            # tokens streamed before the kill carried redispatches == 0
            before = [t for rid, n, t in streamed
                      if rid == r.request_id and n == 0]
            assert before, "prefill streamed a token before the kill"
            # the replay restarted from token 0, marked: the replay-era
            # stream IS the full final output
            replay = [t for rid, n, t in streamed
                      if rid == r.request_id and n == r.redispatches]
            assert replay == r.output_ids
            # and it moved to a different replica
            assert len(r.replica_history) == 2
            assert r.replica_history[0] != r.replica_history[1]

    def test_survivors_zero_steady_state_recompiles(self, chaos):
        fleet, warm = chaos["fleet"], chaos["warm"]
        for rep in (fleet.replicas[0], fleet.replicas[2]):
            eng = rep.engine
            assert eng.metrics.compile_misses == warm[eng.name], \
                f"{eng.name} recompiled during failover"
            assert rep.state == "active" and rep.ejections == 0
            assert eng.health()["kv_block_invariants"] == "ok"

    def test_ejected_replica_rebuilt_and_serves(self, chaos):
        fleet = chaos["fleet"]
        rep = fleet.replicas[1]
        assert rep.state == "active"
        assert rep.ejections == 1 and rep.rebuilds == 1
        assert rep.engine is not chaos["original_r1"]   # fresh engine
        assert chaos["original_r1"].state in ("stopped", "unhealthy")
        st = fleet.stats()
        assert st["supervision"]["ejections"] == 1
        assert st["supervision"]["rebuilds"] == 1
        assert st["supervision"]["last_recovery_ms"] > 0
        assert st["dispatch"]["redispatches"] >= 1
        # the rebuilt replica serves a fresh request with zero extra
        # compiles past its own warmup
        warm_rebuilt = rep.engine.metrics.compile_misses
        r = fleet.submit([1, 2, 3, 4], max_new_tokens=3, replica=1)
        fleet.run()
        assert r.finished and r.replica_history == [rep.engine.name]
        assert rep.engine.metrics.compile_misses == warm_rebuilt
        # exported on the profiler surface
        import paddle_tpu.profiler as profiler

        snap = profiler.serving_fleet()[fleet.name]
        assert snap["supervision"]["ejections"] == 1


class TestFleetDispatch:
    """Prefix-affinity and least-loaded routing, fleet admission
    control, and request validation — on the healed chaos fleet."""

    def test_prefix_affinity_routes_to_cached_replica(self, gpt, chaos):
        fleet = chaos["fleet"]
        rs = np.random.RandomState(7)
        shared = rs.randint(0, 128, (16,)).tolist()     # one whole block
        # seed replica 2's prefix cache (pin bypasses the policy)
        seed = fleet.submit(shared + [1, 2, 3], max_new_tokens=2,
                            replica=2)
        fleet.run()
        assert seed.finished
        assert fleet.replicas[2].engine.prefix_probe(shared + [9]) == 16
        before = fleet.metrics.affinity_hits
        # an unpinned request sharing the prefix must follow it
        r = fleet.submit(shared + [4, 5], max_new_tokens=2)
        assert r.replica_history == [fleet.replicas[2].engine.name]
        fleet.run()
        assert r.finished
        assert fleet.metrics.affinity_hits == before + 1
        assert fleet.metrics.affinity_hit_rate() > 0
        # an unrelated prompt routes least-loaded (no affinity credit)
        r2 = fleet.submit(rs.randint(0, 128, (5,)).tolist(),
                          max_new_tokens=2)
        fleet.run()
        assert r2.finished
        assert fleet.metrics.affinity_hits == before + 1

    def test_fleet_admission_aggregates_queue_depth(self, chaos):
        fleet = chaos["fleet"]
        base_rej = fleet.metrics.rejected
        fleet.max_queue = 2
        try:
            held = [fleet.submit([1, 2], max_new_tokens=2)
                    for _ in range(2)]          # queued, not yet stepped
            with pytest.raises(QueueFull) as qi:
                fleet.submit([3, 4])
            assert qi.value.depth == 2
            assert qi.value.request.state == "rejected"
            assert "across" in qi.value.request.error
        finally:
            fleet.max_queue = None
        fleet.run()
        assert all(r.finished for r in held)
        assert fleet.metrics.rejected == base_rej + 1

    def test_validation_and_cancel(self, chaos):
        fleet = chaos["fleet"]
        with pytest.raises(ValueError) as ei:
            fleet.submit([])
        assert isinstance(ei.value.request, FleetRequest)
        assert ei.value.request.state == "rejected"
        with pytest.raises(ValueError):
            fleet.submit([1, 2], replica=99)
        # cancel mid-flight: terminal exactly once, fleet keeps serving
        r = fleet.submit([5, 6, 7], max_new_tokens=64)
        fleet.step()
        assert r.cancel() is True
        fleet.run()
        assert r.state == "cancelled"
        assert r.cancel() is False
        assert fleet.metrics.duplicate_terminals == 0


class TestFleetResilience:
    def test_redispatch_budget_exhausts_with_replica_error(self, gpt):
        """A fault that kills decode on EVERY replica: the request is
        replayed at most max_redispatch times, then fails carrying the
        replica's recorded error; the fleet heals and serves again."""
        plan = ServingFaultPlan().add("serving.decode", at_call=1,
                                      times=4)
        fleet = Fleet(gpt, num_replicas=2, num_slots=1, max_seq=16,
                      min_bucket=16, eject_after_failures=2,
                      max_redispatch=1, fault_plan=plan)
        terminals = []
        r = fleet.submit([1, 2, 3], max_new_tokens=4,
                         done_cb=lambda fr: terminals.append(fr.state))
        fleet.run()
        assert r.state == "failed"
        assert "redispatch budget exhausted (1)" in r.error
        assert "decode step failed" in r.error      # the replica's error
        assert r.redispatches == 1
        assert terminals == ["failed"]              # exactly once
        st = fleet.stats()
        assert st["supervision"]["ejections"] >= 1
        assert st["supervision"]["rebuilds"] == \
            st["supervision"]["ejections"]
        # the fault window (4 calls) is consumed: the healed fleet serves
        r2 = fleet.submit([4, 5], max_new_tokens=2)
        fleet.run()
        assert r2.finished
        _assert_greedy_chain(gpt, [4, 5], r2.output_ids)
        assert fleet.metrics.duplicate_terminals == 0

    def test_single_replica_fleet_replays_on_its_rebuilt_engine(self, gpt):
        """A 1-replica fleet must not strand replica-implicated
        failures: with no survivor to take the replay, the request is
        held across the supervision pass that ejects + rebuilds the
        sole replica, then replays on the fresh engine and finishes."""
        plan = ServingFaultPlan().add("serving.decode", at_call=2,
                                      times=2)
        fleet = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                      min_bucket=16, eject_after_failures=2,
                      max_redispatch=1, fault_plan=plan)
        terminals = []
        r = fleet.submit([1, 2, 3], max_new_tokens=4,
                         done_cb=lambda fr: terminals.append(fr.state))
        fleet.run()
        assert r.finished, (r.state, r.error)
        assert r.redispatched and r.redispatches == 1
        assert len(r.replica_history) == 2      # same slot, fresh engine
        _assert_greedy_chain(gpt, [1, 2, 3], r.output_ids)
        assert terminals == ["finished"]        # exactly once
        st = fleet.stats()
        assert st["supervision"]["ejections"] == 1
        assert st["supervision"]["rebuilds"] == 1
        assert st["requests"]["duplicate_terminals"] == 0

    def test_cancel_while_parked_for_replay_stays_exactly_once(self, gpt):
        """A request held for post-supervision replay (no survivor) that
        the user cancels between steps must terminate exactly once —
        draining the parked entry must not re-finish it."""
        plan = ServingFaultPlan().add("serving.decode", at_call=2,
                                      times=2)
        # a huge supervise_every keeps the parked entry observable: the
        # reap parks it and no supervision pass replays it yet
        fleet = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                      min_bucket=16, eject_after_failures=2,
                      supervise_every=10 ** 9, fault_plan=plan)
        terminals = []
        r = fleet.submit([1, 2, 3], max_new_tokens=4,
                         done_cb=lambda fr: terminals.append(fr.state))
        # step until the decode fault parks the request for replay
        for _ in range(9):
            fleet.step()
            if fleet._repatriate:
                break
        assert fleet._repatriate and not r.done
        fleet.supervise_every = 1       # resume normal supervision
        assert r.cancel() is True
        assert r.state == "cancelled"
        fleet.run()                     # drains the parked entry
        assert terminals == ["cancelled"]
        assert fleet.metrics.duplicate_terminals == 0
        # shutdown with a parked-but-settled entry is also a no-op
        fleet.shutdown(timeout_s=0.0)
        assert fleet.metrics.duplicate_terminals == 0

    def test_failed_rebuild_retries_before_dead(self, gpt):
        """One transient rebuild failure must not permanently shrink the
        fleet: the replica stays 'ejected' and a later supervision pass
        retries; only MAX_REBUILD_ATTEMPTS consecutive failures kill it."""
        fleet = Fleet(gpt, num_replicas=2, num_slots=1, max_seq=16,
                      min_bucket=16)
        rep = fleet.replicas[0]
        orig = fleet._make_engine
        fail = {"n": 1}                 # fail the first rebuild only
        def flaky(index):
            if index == 0 and fail["n"] > 0:
                fail["n"] -= 1
                raise RuntimeError("transient rebuild failure")
            return orig(index)
        fleet._make_engine = flaky
        assert fleet._eject(rep, "test ejection") == []
        fleet._supervise()              # rebuild attempt #1 fails
        assert rep.state == "ejected" and rep.rebuild_attempts == 1
        assert "1/3" in rep.last_error
        fleet._supervise()              # attempt #2 succeeds
        assert rep.state == "active" and rep.rebuild_attempts == 0
        r = fleet.submit([1, 2], max_new_tokens=2, replica=0)
        fleet.run()
        assert r.finished
        # a replica that keeps failing its rebuild does go dead
        fail["n"] = 10
        assert fleet._eject(rep, "test ejection") == []
        for _ in range(Fleet.MAX_REBUILD_ATTEMPTS):
            fleet._supervise()
        assert rep.state == "dead"
        assert fleet.metrics.rebuild_failures == 1 + 3
        # ...and the fleet keeps serving on the survivor
        r2 = fleet.submit([3, 4], max_new_tokens=2)
        fleet.run()
        assert r2.finished
        assert r2.replica_history == [fleet.replicas[1].engine.name]

    def test_replica_kill_preemption_race_priority_preserved(self, gpt):
        """ISSUE 8 satellite: a replica kill and a preemption race on
        the same request.  A low-priority request is preempted on
        replica 0 by a high-priority arrival (sitting requeued when the
        scoped fault then kills r0's decode), so BOTH the preempted
        victim and the preempting request are orphaned and redispatched
        — terminal exactly once, priority classes preserved verbatim
        across the redispatch, ``duplicate_terminals == 0``."""
        from paddle_tpu.serving import PRIORITY_HIGH, PRIORITY_LOW

        plan = ServingFaultPlan().add("serving.r0.decode", at_call=4,
                                      times=2)
        fleet = Fleet(gpt, num_replicas=2, num_slots=1, max_seq=32,
                      min_bucket=16, block_size=16,
                      eject_after_failures=2, max_redispatch=2,
                      fault_plan=plan)
        fleet.warmup()
        terminals = []
        rs = np.random.RandomState(21)
        p_lo = rs.randint(0, 128, (5,)).tolist()
        p_hi = rs.randint(0, 128, (6,)).tolist()
        low = fleet.submit(p_lo, max_new_tokens=6, priority="low",
                           replica=0,
                           done_cb=lambda r: terminals.append(
                               r.request_id))
        fleet.step()                    # low admitted on r0, decode #1
        assert low._attempt is not None and low._attempt.state == "running"
        high = fleet.submit(p_hi, max_new_tokens=6, priority="high",
                            replica=0,
                            done_cb=lambda r: terminals.append(
                                r.request_id))
        fleet.step()                    # high preempts low on r0 (1 slot)
        assert low._attempt.preempted and low._attempt.state == "queued"
        # drive until the scoped fault kills r0 (decode call 4, both
        # retries) and both requests land redispatched on the survivor;
        # capture the replayed attempts' engine-level priorities live
        replay_prio = {}
        for _ in range(60):
            fleet.step()
            for freq in (low, high):
                att = freq._attempt
                if freq.redispatches > 0 and att is not None:
                    replay_prio[freq.request_id] = att.priority
            if low.done and high.done:
                break
        fleet.run()
        st = fleet.stats()
        # terminal exactly once, both finished with full greedy outputs
        assert sorted(terminals) == sorted(
            [low.request_id, high.request_id])
        assert st["requests"]["duplicate_terminals"] == 0
        for p, r in ((p_lo, low), (p_hi, high)):
            assert r.finished and len(r.output_ids) == 6
            assert r.redispatches == 1 and len(r.replica_history) == 2
            _assert_greedy_chain(gpt, p, r.output_ids)
        # the decode-killed request replays on the SURVIVOR; the
        # preempted victim (exported while queued) may land on either
        # the survivor or the rebuilt replica — both are fresh engines
        assert high.replica_history[0].endswith(".r0")
        assert high.replica_history[1] == fleet.replicas[1].engine.name
        # priority classes preserved verbatim across the redispatch
        assert replay_prio[low.request_id] == PRIORITY_LOW
        assert replay_prio[high.request_id] == PRIORITY_HIGH
        assert low.kwargs["priority"] == "low"
        # the ejected engine's preemption was banked into the fleet
        # aggregate before its rebuild wiped the live counter
        assert st["overload"]["preemptions"] >= 1
        assert st["supervision"]["ejections"] == 1
        assert st["supervision"]["rebuilds"] == 1
        fleet.shutdown(timeout_s=0.0)

    def test_fleet_shed_counted_on_mixed_rejection(self, gpt):
        """A replica shed during the dispatch hunt is counted in the
        fleet shed aggregate (once per submit) even when the FINAL
        rejection the hunt surfaces is another replica's plain
        QueueFull.  Host-only: nothing here compiles."""
        from paddle_tpu.serving import ShedReject

        fleet = Fleet(gpt, num_replicas=2, num_slots=1, max_seq=16,
                      min_bucket=16)
        # r0 (least loaded → tried first): deep backlog + ITL history,
        # sheds any hopeless-deadline admission
        fleet.submit([1, 2], max_new_tokens=16, replica=0)
        fleet.replicas[0].engine.metrics.itl_s.extend([0.05] * 20)
        # r1: at its engine-level queue bound → plain QueueFull
        for _ in range(2):
            fleet.submit([3, 4], max_new_tokens=4, replica=1)
        fleet.replicas[1].engine.max_queue = 2
        with pytest.raises(QueueFull) as qi:
            fleet.submit([5, 6], max_new_tokens=4, deadline_s=0.001)
        assert not isinstance(qi.value, ShedReject)  # r1's rejection won
        assert qi.value.request.state == "rejected"
        assert fleet.stats()["overload"]["shed"] == 1
        fleet.shutdown(timeout_s=0.0)

    def test_fleet_queue_full_retry_after_uses_request_priority(
            self, gpt):
        """The fleet backpressure ``retry_after_s`` is priced at the
        rejected request's own priority class, same as the engine-level
        path: a high request only waits behind the >=-high backlog."""
        fleet = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                      min_bucket=16, max_queue=1)
        fleet.submit([1, 2], max_new_tokens=16)      # normal backlog
        fleet.replicas[0].engine.metrics.itl_s.extend([0.05] * 10)
        with pytest.raises(QueueFull) as hi:
            fleet.submit([3, 4], priority="high")
        assert hi.value.retry_after_s == 0.0   # nothing queued at >= high
        with pytest.raises(QueueFull) as lo:
            fleet.submit([3, 4], priority="low")
        assert lo.value.retry_after_s > 0.0    # waits behind the normal
        assert lo.value.request.error_ctx["retry_after_s"] == \
            lo.value.retry_after_s
        # a malformed priority on a FULL fleet still rejects the handle
        # exactly once (never a pending request the fleet lost track of)
        done = []
        with pytest.raises(ValueError) as vi:
            fleet.submit([3, 4], priority="urgent",
                         done_cb=done.append)
        assert vi.value.request.state == "rejected"
        assert [r.request_id for r in done] == [vi.value.request
                                                .request_id]
        fleet.shutdown(timeout_s=0.0)

    def test_engine_export_requests_hook(self, gpt):
        """The ejection hook: queued + in-flight requests come back in
        scheduling order, retired replica-kind, slots reclaimed."""
        from paddle_tpu.serving import Engine

        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16)
        r1 = eng.add_request([1, 2], max_new_tokens=8)
        r2 = eng.add_request([3, 4], max_new_tokens=8)
        eng.step()                      # r1 running, r2 queued
        out = eng.export_requests()
        assert out == [r2, r1]          # queue first, then running
        for r in (r1, r2):
            assert r.state == "cancelled" and r.error_kind == "replica"
            assert "ejection" in r.error
        assert sorted(eng.free_slots) == [0]
        assert not eng.queue and not eng.running
        assert eng.export_requests() == []          # idempotent

    def test_submit_with_no_dispatchable_replica_rejects_handle(self, gpt):
        """A submit no replica can take must still terminate its handle
        (rejected, exactly once, attached to the exception) — never a
        dangling 'pending' request the fleet no longer tracks."""
        fleet = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                      min_bucket=16)
        fleet.replicas[0].state = "ejected"     # rotation is empty
        with pytest.raises(EngineStopped) as ei:
            fleet.submit([1, 2], max_new_tokens=2)
        r = ei.value.request
        assert isinstance(r, FleetRequest) and r.state == "rejected"
        assert "no active replica" in r.error
        with pytest.raises(EngineStopped) as ei2:
            fleet.submit([1, 2], max_new_tokens=2, replica=0)  # pinned
        assert ei2.value.request.state == "rejected"
        assert fleet.pending == 0
        assert fleet.metrics.submitted == 2 == fleet.metrics.rejected
        assert fleet.metrics.duplicate_terminals == 0

    def test_drain_max_steps_still_reaps_engine_drained_work(self, gpt):
        """drain(max_steps=N) too small to cover the workload: the
        engine-level drains finish the work, and the fleet must reap it
        — every handle terminal, every done_cb fired, pending == 0."""
        fleet = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                      min_bucket=16)
        done = []
        reqs = [fleet.submit([i + 1, i + 2], max_new_tokens=2,
                             done_cb=lambda fr: done.append(fr.request_id))
                for i in range(2)]
        st = fleet.drain(max_steps=1)
        assert all(r.finished for r in reqs)
        assert sorted(done) == [r.request_id for r in reqs]
        assert fleet.state == "stopped" and st["pending"] == 0
        assert fleet.metrics.duplicate_terminals == 0

    def test_fleet_drain_and_shutdown(self, gpt):
        fleet = Fleet(gpt, num_replicas=2, num_slots=1, max_seq=16,
                      min_bucket=16)
        reqs = [fleet.submit([i, i + 1], max_new_tokens=2)
                for i in range(3)]
        st = fleet.drain()
        assert all(r.finished for r in reqs)
        assert fleet.state == "stopped" and st["pending"] == 0
        assert all(rep.engine.state == "stopped"
                   for rep in fleet.replicas)
        with pytest.raises(EngineStopped):
            fleet.submit([1, 2])
        # shutdown with a zero budget cancels in-flight work exactly once
        fleet2 = Fleet(gpt, num_replicas=1, num_slots=1, max_seq=16,
                       min_bucket=16)
        r = fleet2.submit([7, 8], max_new_tokens=64)
        fleet2.step()
        st2 = fleet2.shutdown(timeout_s=0.0)
        assert r.state == "cancelled" and r.error == "fleet shutdown"
        assert fleet2.state == "stopped"
        assert st2["requests"]["cancelled"] == 1
        assert st2["requests"]["duplicate_terminals"] == 0

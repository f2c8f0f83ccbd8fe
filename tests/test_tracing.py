"""Request-lifecycle tracing, flight recorder & obs exporters (ISSUE 9).

The chaos-run chain validation rides the session-scope ``fleet_chaos``
fixture (tests/conftest.py) — the SAME 3-replica ejection/redispatch
run test_fleet.py asserts failover semantics on, so tracing coverage
adds no second fleet to the tier-1 budget.  The preempt/shed span tests
share one small compiled paged engine.  Tier-1 critical:
tools/collect_gate.py fails CI if this file stops collecting or grows a
``slow`` mark.
"""
import json
import time

import pytest

import paddle_tpu.profiler as profiler
from paddle_tpu import obs
from paddle_tpu.serving import (
    Engine, FlightRecorder, NULL_TRACER, QueueFull, RequestTracer,
    ServingMetrics, FleetMetrics, validate_trace,
)


@pytest.fixture(scope="module")
def traced(serving_model):
    """One shared compiled paged engine with a live tracer (1 slot so
    preemption is forced; aging off so ordering is explicit)."""
    tr = RequestTracer()
    eng = Engine(serving_model, num_slots=1, max_seq=32, min_bucket=8,
                 block_size=8, priority_aging_s=None,
                 tracer=tr)
    eng.warmup()
    return eng, tr


class TestChaosTraceChain:
    """ISSUE 9 acceptance: every request in the chaos run has exactly
    one terminal event, and preempt/redispatch spans link parent→child
    correctly across replicas."""

    def test_chain_validator_clean(self, fleet_chaos):
        problems = validate_trace(fleet_chaos["tracer"])
        assert problems == [], problems

    def test_every_request_exactly_one_terminal(self, fleet_chaos):
        tr, fleet = fleet_chaos["tracer"], fleet_chaos["fleet"]
        for req in fleet_chaos["reqs"]:
            trace = f"{fleet.name}:f{req.request_id}"
            finals = [ev for ev in tr.events
                      if ev["kind"] == "retired" and ev.get("final")
                      and ev.get("trace") == trace]
            assert len(finals) == 1, (trace, finals)
            assert finals[0]["state"] == "finished"

    def test_redispatch_spans_link_parent_child_across_replicas(
            self, fleet_chaos):
        tr = fleet_chaos["tracer"]
        moved = [r for r in fleet_chaos["reqs"] if r.redispatches > 0]
        assert moved, "the scoped fault must have orphaned requests"
        fleet = fleet_chaos["fleet"]
        for r in moved:
            trace = f"{fleet.name}:f{r.request_id}"
            attempts = sorted(
                (s for s in tr.spans.values()
                 if s["trace"] == trace and s["name"] == "attempt"),
                key=lambda s: s["id"])
            assert len(attempts) >= 2
            first, last = attempts[0], attempts[-1]
            # the replay chains off the interrupted attempt, on a
            # DIFFERENT replica, and only the last attempt finishes
            assert last["parent"] == attempts[-2]["id"]
            assert first["replica"] != last["replica"]
            assert last["state"] == "finished"
            assert first["state"] in ("failed", "exported")
            # the root span parents the first attempt
            root = tr.spans[first["parent"]]
            assert root["name"] == "request" and root["state"] == \
                "finished"

    def test_eject_rebuild_events_recorded(self, fleet_chaos):
        tr = fleet_chaos["tracer"]
        kinds = [ev["kind"] for ev in tr.events]
        assert "eject" in kinds and "rebuild" in kinds
        ej = next(ev for ev in tr.events if ev["kind"] == "eject")
        assert ej["replica"].endswith(".r1")
        rb = next(ev for ev in tr.events if ev["kind"] == "rebuild")
        assert rb["ok"] and rb["recovery_ms"] > 0

    def test_decode_steps_are_batched_per_engine_step(self, fleet_chaos):
        tr = fleet_chaos["tracer"]
        steps = [ev for ev in tr.events if ev["kind"] == "decode_step"]
        assert steps, "no decode-step events recorded"
        # one event per ENGINE STEP, not per token: each carries the
        # whole active batch, so events << decoded tokens whenever
        # slots run concurrently, and n_active always matches the batch
        assert all(ev["n_active"] == len(ev["slots"]) >= 1
                   for ev in steps)
        decoded = sum(ev["n_active"] for ev in steps)
        assert len(steps) < decoded  # batching actually batched

    def test_events_monotonic_and_wall_free(self, fleet_chaos):
        tr = fleet_chaos["tracer"]
        ts = [ev["ts"] for ev in tr.events]
        assert ts == sorted(ts)
        # wall-clock exists ONLY in exported records, never in events
        assert all("wall" not in ev for ev in tr.events)
        assert tr.dropped == 0

    def test_flight_dump_banked_on_ejection(self, fleet_chaos):
        fleet = fleet_chaos["fleet"]
        rep = fleet.replicas[1]
        assert rep.flight_dumps, "ejection must freeze a flight dump"
        d = rep.flight_dumps[-1]
        assert "ejected" in d["reason"]
        assert d["name"].endswith(".r1")
        # attached to the rebuild record (replica row summary)...
        row = fleet.stats()["replicas"][1]
        assert row["last_flight_record"]["reason"] == d["reason"]
        # ...and surfaced process-wide even though the ejected engine
        # itself was discarded
        fr = profiler.serving_flight_record()
        assert any("ejected" in dump["reason"]
                   for snap in fr.get(rep.engine.name, [])
                   for dump in snap.get("dumps", []))


class TestPreemptResumeSpans:
    def test_preempt_links_resume_span_and_cheap_resume(self, traced):
        eng, tr = traced
        warm = eng.metrics.compile_misses
        lo = eng.add_request(list(range(1, 10)), max_new_tokens=6,
                             priority="low")
        eng.step()                       # lo admitted (bucket 16)
        hi = eng.add_request([4, 5, 6], max_new_tokens=4,
                             priority="high")
        eng.run()
        assert lo.finished and hi.finished and lo.preempted
        assert eng.metrics.compile_misses == warm  # zero new keys
        trace = f"{eng.name}:r{lo.request_id}"
        pre = [ev for ev in tr.events if ev["kind"] == "preempt"
               and ev["trace"] == trace]
        assert len(pre) == 1
        resume = tr.spans[pre[0]["resume_span"]]
        assert resume["parent"] == pre[0]["span"]
        assert resume["name"] == "resume"
        assert tr.spans[pre[0]["span"]]["state"] == "preempted"
        assert resume["state"] == "finished"
        # cheap resume is VISIBLE in the chain: the victim's prompt
        # blocks were registered before its slot released, so the
        # resume admission hits the prefix cache and the tail bucket
        # shrinks (16 -> 8)
        admits = [ev for ev in tr.events if ev["kind"] == "admitted"
                  and ev["trace"] == trace]
        assert admits[0]["prefix_hit"] == 0 and admits[0]["bucket"] == 16
        assert admits[-1]["span"] == resume["id"]
        assert admits[-1]["prefix_hit"] == 8 and admits[-1]["bucket"] == 8
        assert validate_trace(tr) == []

    def test_shed_trace_terminates_exactly_once(self, traced):
        eng, tr = traced
        runner = eng.add_request(list(range(10, 19)), max_new_tokens=24)
        eng.step()                       # occupy the only slot
        queued = [eng.add_request(list(range(20, 29)), max_new_tokens=24)
                  for _ in range(2)]
        eng.metrics.itl_s.extend([0.05] * 50)
        with pytest.raises(QueueFull) as ei:
            eng.add_request([1, 2, 3], max_new_tokens=4,
                            deadline_s=0.01)
        shed_req = ei.value.request
        eng.run()                        # drain so every span closes
        assert runner.finished and all(q.finished for q in queued)
        trace = f"{eng.name}:r{shed_req.request_id}"
        evs = [ev for ev in tr.events if ev.get("trace") == trace]
        assert [ev["kind"] for ev in evs] == ["shed", "retired"]
        assert evs[0]["estimated_wait_s"] > 0.01
        assert evs[1]["final"] and evs[1]["state"] == "rejected"
        assert validate_trace(tr) == []

    def test_block_pressure_events_on_defer(self, serving_model):
        """A paged pool too small for two concurrent prompts: the
        second admission defers and the tracer records the pressure."""
        tr = RequestTracer()
        eng = Engine(serving_model, num_slots=2, max_seq=16,
                     min_bucket=16, block_size=8,
                     num_kv_blocks=3, max_preemptions=0, tracer=tr)
        # no warmup/compile needed: admission bookkeeping happens before
        # the prefill call, and we only step once with a doomed pool
        r1 = eng.add_request([1, 2, 3], max_new_tokens=2)
        r2 = eng.add_request([4, 5, 6], max_new_tokens=2)
        eng.step()
        pressure = [ev for ev in tr.events
                    if ev["kind"] == "block_pressure"]
        assert pressure and pressure[0]["pressure"] == "defer"
        assert r1.state in ("running", "finished")
        assert not r2.done or r2.state == "failed"
        eng.shutdown(timeout_s=0.0)


class TestDisabledTracerAndEnv:
    def test_default_engine_tracer_is_noop(self, serving_model):
        eng = Engine(serving_model, num_slots=1, max_seq=16,
                     min_bucket=16)
        assert eng.tracer is NULL_TRACER
        assert NULL_TRACER.enabled is False
        r = eng.add_request([1, 2, 3], max_new_tokens=2)
        # every hook is a shared no-op: nothing recorded anywhere
        assert NULL_TRACER.events == () and NULL_TRACER.dropped == 0
        assert NULL_TRACER.on_queued(r, "x") is None
        assert "tracing" not in eng.stats()
        r.cancel()

    def test_from_env(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_TRACE", raising=False)
        assert RequestTracer.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
        assert RequestTracer.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
        assert isinstance(RequestTracer.from_env(), RequestTracer)
        monkeypatch.setenv("PADDLE_TPU_TRACE", "sometimes")
        with pytest.raises(ValueError, match="PADDLE_TPU_TRACE"):
            RequestTracer.from_env()

    def test_bounded_events_fail_validation(self):
        tr = RequestTracer(max_events=2)
        for _ in range(5):
            tr._event("decode_step", replica="x", n_active=1, slots=[0])
        assert len(tr.events) == 2 and tr.dropped == 3
        assert any("dropped" in p for p in validate_trace(tr))

    def test_validator_rejects_broken_chains(self):
        tr = RequestTracer()
        sid = tr._begin_span("t1", "attempt")
        tr._event("retired", trace="t1", span=sid, final=True,
                  state="finished")
        tr._event("retired", trace="t1", span=sid, final=True,
                  state="finished")
        problems = validate_trace(tr)
        assert any("2 terminal events" in p for p in problems)
        assert any("never ended" in p for p in problems)


class TestExporters:
    def test_chrome_trace_is_perfetto_loadable(self, fleet_chaos,
                                               tmp_path):
        tr = fleet_chaos["tracer"]
        ct = obs.chrome_trace(tr)
        # JSON-serializable with the trace-event essentials
        blob = json.dumps(ct)
        assert json.loads(blob)["displayTimeUnit"] == "ms"
        te = ct["traceEvents"]
        procs = {e["args"]["name"] for e in te
                 if e["ph"] == "M" and e["name"] == "process_name"}
        # one track group per replica plus the router
        assert "router" in procs
        assert {p for p in procs if ".r" in p} == {
            rep.engine.name for rep in fleet_chaos["fleet"].replicas}
        spans = [e for e in te if e["ph"] == "X"]
        assert len(spans) == len(tr.spans)
        assert all(e["dur"] >= 0 for e in spans)
        # redispatch links render as flow arrows across replicas
        assert [e for e in te if e["ph"] == "s"] and \
            [e for e in te if e["ph"] == "f"]
        # batched decode steps become a counter track
        assert any(e["ph"] == "C" and e["name"] == "active_slots"
                   for e in te)
        path = obs.write_chrome_trace(tr, str(tmp_path / "trace.json"))
        with open(path) as f:
            assert json.load(f)["traceEvents"]

    def test_jsonl_export_adds_wall_clock(self, fleet_chaos, tmp_path):
        tr = fleet_chaos["tracer"]
        path = str(tmp_path / "events.jsonl")
        n = obs.write_jsonl(tr, path)
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert n == len(lines) == len(tr.events)
        now = time.time()
        for ln in lines[:20]:
            assert abs(ln["wall"] - (tr.wall0 + ln["ts"])) < 1e-6
            assert ln["wall"] <= now + 1
        assert [ln["ts"] for ln in lines] == sorted(
            ln["ts"] for ln in lines)

    def test_metrics_text_exposition(self, traced):
        eng, _tr = traced
        text = obs.render_metrics(eng.stats())
        assert f'engine="{eng.name}"' in text
        for needle in ("paddle_tpu_serving_queue_depth",
                       "paddle_tpu_serving_requests_completed",
                       "paddle_tpu_serving_compile_cache_misses",
                       "paddle_tpu_serving_health_state_info"):
            assert needle in text, (needle, text[:400])
        # every sample line is name{labels} value with a numeric value
        for line in text.strip().splitlines():
            name_part, value = line.rsplit(" ", 1)
            float(value)
            assert name_part.startswith("paddle_tpu_serving")
        assert "paddle_tpu_serving" in obs.render_all_metrics()


class TestSnapshotIsolation:
    """ISSUE 9 satellite: mutating a snapshot can never corrupt live
    metric state (copy-on-read regression)."""

    def test_serving_metrics_snapshot_is_isolated(self):
        m = ServingMetrics("iso-test", num_slots=2)
        m.on_retry("serving.decode")
        m.on_admit(16, 9, 0)
        snap = m.snapshot()
        snap["failures"]["retries_by_point"]["serving.decode"] = 999
        snap["failures"]["retries_by_point"]["injected"] = 1
        snap["prefills_by_bucket"][16] = 999
        snap["requests"]["admitted"] = 999
        snap["ttft_ms"]["count"] = 999
        fresh = m.snapshot()
        assert fresh["failures"]["retries_by_point"] == \
            {"serving.decode": 1}
        assert fresh["prefills_by_bucket"] == {16: 1}
        assert fresh["requests"]["admitted"] == 1
        assert m.retries_by_point == {"serving.decode": 1}

    def test_fleet_metrics_snapshot_is_isolated(self):
        fm = FleetMetrics("iso-fleet", num_replicas=2)
        rows = [{"index": 0, "nested": {"k": 1}}]
        fm.replicas_cb = lambda: rows
        snap = fm.snapshot()
        snap["replicas"][0]["nested"]["k"] = 999
        snap["requests"]["completed"] = 999
        assert rows[0]["nested"]["k"] == 1
        assert fm.snapshot()["requests"]["completed"] == 0

    def test_engine_stats_paging_section_is_isolated(self, traced):
        eng, _tr = traced
        snap = eng.stats()
        before = eng.cache.allocator.stats()["free"]
        snap["paging"]["blocks"]["free"] = -12345
        snap["health"]["kv_blocks"]["free"] = -12345
        assert eng.cache.allocator.stats()["free"] == before
        assert eng.stats()["paging"]["blocks"]["free"] == before


class TestFlightRecorder:
    def test_ring_bound_and_dumps(self):
        rec = FlightRecorder(capacity=4, name="fr-test", max_dumps=2)
        for i in range(10):
            rec.record(step=i)
        snap = rec.snapshot()
        assert snap["ring_depth"] == 4 and snap["steps_seen"] == 10
        for i in range(3):
            rec.dump(f"reason {i}")
        assert [d["reason"] for d in rec.dumps] == ["reason 1",
                                                    "reason 2"]
        d = rec.dumps[-1]
        assert [e["step"] for e in d["events"]] == [6, 7, 8, 9]
        assert d["wall_time"] == pytest.approx(time.time(), abs=60)
        # snapshots are copies: mutating one can't corrupt the recorder
        snap2 = rec.snapshot()
        snap2["dumps"][0]["events"].clear()
        assert rec.dumps[0]["events"]

    def test_engine_dumps_on_unhealthy(self, serving_model):
        eng = Engine(serving_model, num_slots=1, max_seq=16,
                     min_bucket=16)
        assert eng.flight.dumps == []
        eng._mark_block_corruption("induced for test")
        assert eng.state == "unhealthy"
        assert len(eng.flight.dumps) == 1
        assert "induced for test" in eng.flight.dumps[0]["reason"]
        fr = profiler.serving_flight_record()
        assert any("induced for test" in d["reason"]
                   for snap in fr.get(eng.name, [])
                   for d in snap.get("dumps", []))

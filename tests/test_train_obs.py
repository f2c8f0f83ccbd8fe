"""Training step observatory (ISSUE 13, docs/OBSERVABILITY.md).

The acceptance bar:

- a ``StepTimeline`` under a ``to_static`` training run with an
  injected ``train.nan`` rollback is CHAIN-VALID: the rollback is
  present as a ``rolled_back`` attempt span linked to the attempt that
  resumed from it, every attempt has exactly one terminal, and the
  Perfetto/JSONL exports are well-formed;
- the ``CompileLedger`` records every executable-cache miss with wall
  seconds and an attributed call site, catches a deliberately churned
  shape as a NAMED steady-state anomaly, and stays flat in steady
  state;
- the ``CostLedger``'s XLA flop count for the tiny-GPT train step is
  within tolerance of the 6ND analytic count, its analytic roofline
  MFU is sane, and its schedule fingerprint is bitwise-stable across
  identical analyses;
- attaching the WHOLE observatory (timeline + compile ledger + cost
  analysis) adds ZERO executable-cache keys (key-set equality);
- the training stats flow into ``profiler.train_stats()`` and the
  one-process metrics exposition next to the serving snapshots.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import obs, profiler
from paddle_tpu.distributed.fault_tolerance import (
    DivergenceSentry, FaultPlan, ResilientLoop, global_grad_norm)
from paddle_tpu.obs import (NULL_TIMELINE, CompileLedger, CostLedger,
                            StepTimeline, validate_timeline)
from paddle_tpu.core.chip import chip_peaks
from paddle_tpu.obs.hlo_cost import count_hlo_ops, schedule_fingerprint

# these programs compile on the CPU; the analytic roofline projects them
# onto a chip the test names (never one inherited from a default)
CHIP = "TPU v5 lite"


def _sentry(**kw):
    kw.setdefault("window", 8)
    kw.setdefault("min_history", 2)
    kw.setdefault("spike_factor", 8.0)
    kw.setdefault("grad_ratio", 100.0)
    kw.setdefault("snapshot_every", 2)
    kw.setdefault("ring_capacity", 2)
    kw.setdefault("max_rollbacks", 2)
    return DivergenceSentry(**kw)


def _rig(seed=7):
    paddle.seed(seed)
    net = nn.Linear(8, 8)
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=net.parameters())
    sentry = _sentry()

    @paddle.jit.to_static
    def train_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        sentry.observe(loss, grad_norm=global_grad_norm(net.parameters()))
        opt.step()
        opt.clear_grad()
        return loss

    return net, opt, sentry, train_step


def _run_nan_drill(tmp_path, timeline, compile_ledger=None, steps=8,
                   nan_at=5, cost_ledger=None):
    net, opt, sentry, train_step = _rig()
    plan = FaultPlan().add_train_fault("train.nan", nan_at)

    def step_fn(step):
        rs = np.random.RandomState(100 + step)
        x = plan.corrupt_batch(step, rs.randn(4, 8).astype(np.float32))
        train_step(paddle.to_tensor(x))

    loop = ResilientLoop(
        str(tmp_path / "ck"),
        state_fn=lambda: {"m": net.state_dict(), "o": opt.state_dict()},
        restore_fn=lambda s: (net.set_state_dict(s["m"]),
                              opt.set_state_dict(s["o"])),
        save_every=None, save_final=False, sentry=sentry, verbose=False,
        timeline=timeline, compile_ledger=compile_ledger,
        cost_ledger=cost_ledger)
    loop.run(step_fn, steps)
    return loop, sentry, train_step


class TestStepTimeline:
    def test_loop_nan_rollback_chain_valid(self, tmp_path):
        """The tentpole bar: a to_static train loop with an injected
        train.nan rollback produces a chain-valid timeline — rollback
        attempt span present and linked, one terminal per attempt."""
        tl = StepTimeline()
        loop, sentry, _ = _run_nan_drill(tmp_path, tl)
        assert sentry.rollbacks == 1 and sentry.skipped_steps == 1
        assert validate_timeline(tl) == []
        rolled = [s for s in tl.spans.values()
                  if s["state"] == "rolled_back"]
        assert len(rolled) == 1 and rolled[0]["name"] == "step"
        skipped = [s for s in tl.spans.values()
                   if s["state"] == "skipped"]
        assert len(skipped) == 1
        # every attempt trace has exactly one root (= one terminal)
        roots = {}
        for s in tl.spans.values():
            if s["parent"] is None:
                roots.setdefault(s["trace"], []).append(s)
        assert all(len(v) == 1 for v in roots.values())
        # the rollback event links to the attempt that resumed from it
        rb = [e for e in tl.events if e["kind"] == "rollback"]
        assert len(rb) == 1
        resume = tl.spans[rb[0]["resume_span"]]
        assert resume["name"] == "step"
        assert resume["t_start"] >= rb[0]["ts"]
        # counters add up: 7 unique completed steps + step 4 replayed
        # after the rollback, 1 skipped window
        c = tl.counters()
        assert c["steps_completed"] == 8 and c["skipped"] == 1
        assert c["rolled_back"] == 1
        # phase accounting saw the loop's phases
        for ph in ("step_dispatch", "device_wait", "snapshot_capture",
                   "rollback_restore"):
            assert c["phase_ms"].get(ph, 0) > 0, ph

    def test_perfetto_and_jsonl_exports_well_formed(self, tmp_path):
        tl = StepTimeline()
        _run_nan_drill(tmp_path, tl)
        chrome = obs.chrome_trace(tl)
        json.dumps(chrome)               # Perfetto loads plain JSON
        evs = chrome["traceEvents"]
        # process named after the timeline, one thread per phase
        procs = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert procs == {"trainer"}
        threads = {e["args"]["name"] for e in evs
                   if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert {"step", "step_dispatch", "device_wait",
                "snapshot_capture", "rollback_restore"} <= threads
        # the injected rollback is a span in the export, with its flow
        # arrow (s/f pair) into the resumed attempt
        rolled = [e for e in evs if e.get("ph") == "X"
                  and e.get("args", {}).get("state") == "rolled_back"]
        assert rolled
        flows = [e for e in evs if e.get("ph") in ("s", "f")
                 and e.get("name") == "rollback"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        # JSONL: one valid object per line, wall stamped at export
        lines = list(obs.jsonl_lines(tl))
        assert len(lines) == len(tl.events)
        for ln in lines:
            rec = json.loads(ln)
            assert rec["wall"] >= tl.wall0

    def test_fit_timeline_chain_valid_with_rollback(self):
        """hapi fit + sentry + timeline: a poisoned batch rolls back
        and the batch-attempt chain stays valid, with data_fetch /
        step_dispatch / device_wait phases recorded."""
        paddle.seed(21)
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     parameters=net.parameters())
        model.prepare(optimizer=opt,
                      loss=lambda out, y: ((out - y) ** 2).mean())
        rs = np.random.RandomState(3)
        data = []
        for i in range(10):
            x = rs.randn(4).astype(np.float32)
            if i == 5:
                x = x * np.float32("nan")
            data.append((x, rs.randn(2).astype(np.float32)))
        tl = StepTimeline()
        sentry = _sentry(min_history=3, spike_factor=50.0)
        model.fit(data, epochs=1, batch_size=1, verbose=0, shuffle=False,
                  sentry=sentry, timeline=tl)
        assert sentry.rollbacks == 1
        assert validate_timeline(tl) == []
        assert tl.counters()["rolled_back"] == 1
        assert tl.counters()["steps_completed"] == 9
        for ph in ("data_fetch", "step_dispatch", "device_wait",
                   "snapshot_capture", "rollback_restore"):
            assert tl.phase_seconds.get(ph, 0) > 0, ph
        # an armed fit joins the process-wide observatory surface
        # exactly like a ResilientLoop (review regression: it used to
        # be silently absent from the documented exposition)
        stats = profiler.train_stats()
        fit_snaps = [s for s in stats.values() if s.get("name") == "fit"]
        assert fit_snaps and fit_snaps[0]["timeline"]["rolled_back"] == 1
        assert fit_snaps[0]["sentry"]["rollbacks"] == 1

    def test_null_timeline_is_inert(self):
        assert not NULL_TIMELINE.enabled
        with NULL_TIMELINE.phase("anything"):
            pass
        NULL_TIMELINE.begin_step(0)
        NULL_TIMELINE.end_step()
        NULL_TIMELINE.on_rollback(0)
        assert NULL_TIMELINE.counters() == {}
        assert NULL_TIMELINE.snapshot() == {}
        assert list(NULL_TIMELINE.events) == []
        # the hook set is EXPLICIT: a misspelled hook call fails in
        # unarmed runs too, instead of only for users who arm tracing
        with pytest.raises(AttributeError):
            NULL_TIMELINE.on_skipped(0)
        # exporting an UNARMED loop's timeline is a valid empty trace,
        # not a crash deep in json (review regression: __getattr__
        # handed the exporters a function for wall0)
        chrome = obs.chrome_trace(NULL_TIMELINE)
        json.dumps(chrome)
        assert chrome["traceEvents"] == []
        assert list(obs.jsonl_lines(NULL_TIMELINE)) == []

    def test_validator_rejects_broken_chains(self):
        tl = StepTimeline()
        tl.begin_step(0)                      # never ended
        assert any("never ended" in p for p in validate_timeline(tl))
        tl.end_step("completed")
        assert validate_timeline(tl) == []
        # a rollback whose resume link is missing while later attempts
        # exist is a broken chain
        tl2 = StepTimeline()
        tl2.begin_step(0)
        tl2.on_rollback(0)
        tl2._pending_rollback = None          # sever the link
        tl2.begin_step(1)
        tl2.end_step("completed")
        assert any("no resume link" in p for p in validate_timeline(tl2))
        # ...but a rollback as the run's last act is legal
        tl3 = StepTimeline()
        tl3.begin_step(0)
        tl3.on_rollback(0)
        assert validate_timeline(tl3) == []

    def test_timeline_cap_counts_drops(self):
        tl = StepTimeline(max_events=3)
        for i in range(6):
            tl.begin_step(i)
            tl.end_step()
        assert tl.dropped > 0
        assert any("dropped" in p for p in validate_timeline(tl))

    def test_env_arming(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_TRAIN_TRACE", raising=False)
        assert StepTimeline.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_TRAIN_TRACE", "1")
        assert isinstance(StepTimeline.from_env(), StepTimeline)
        monkeypatch.setenv("PADDLE_TPU_TRAIN_TRACE", "bogus")
        with pytest.raises(ValueError):
            StepTimeline.from_env()

    def test_abandon_undoes_attempt_bookkeeping(self):
        """Review regression: fit's epoch boundary abandons the fetch
        attempt at gstep N, then epoch 2 re-begins the SAME gstep N —
        that must export as first attempt ``sN``, not a phantom
        ``sN#2`` rollback replay, and a rollback-free multi-epoch run
        must keep the replay table empty."""
        tl = StepTimeline()
        for s in (0, 1):
            tl.begin_step(s)
            tl.end_step()
        tl.begin_step(2)                 # epoch 1's exhausted fetch
        with tl.phase("data_fetch"):
            pass
        tl.abandon_step()
        tl.begin_step(2)                 # epoch 2's first real batch
        tl.end_step()
        assert validate_timeline(tl) == []
        traces = {sp["trace"] for sp in tl.spans.values()}
        assert "trainer:s2" in traces
        assert not any("#" in t for t in traces), traces
        assert tl._attempts == {}

    def test_abandon_rearms_pending_rollback_link(self):
        """Review regression: a rollback on the epoch's last batch
        links its resume to the NEXT attempt — which data_fetch then
        abandons on StopIteration.  The abandoned span must not leave
        a dangling resume link: it re-arms onto the following attempt
        (next epoch), or legally stays absent when the run ends."""
        tl = StepTimeline()
        tl.begin_step(0)
        tl.on_rollback(0)
        tl.begin_step(1)             # rollback links here...
        with tl.phase("data_fetch"):
            pass
        tl.abandon_step()            # ...but the attempt never ran
        assert validate_timeline(tl) == []     # run-over: link absent
        tl.begin_step(2)             # next epoch: link re-armed here
        tl.end_step("completed")
        assert validate_timeline(tl) == []
        rb = [e for e in tl.events if e["kind"] == "rollback"][0]
        assert tl.spans[rb["resume_span"]]["trace"].endswith("s2")

    def test_fit_env_armed_timeline(self, monkeypatch):
        """fit honors the PADDLE_TPU_TRAIN_TRACE arming path exactly
        like ResilientLoop does (review regression: it used to fall
        back straight to NULL_TIMELINE without consulting from_env)."""
        from paddle_tpu.obs import train as train_mod

        tl = StepTimeline()
        monkeypatch.setattr(train_mod.StepTimeline, "from_env",
                            classmethod(lambda cls: tl))
        paddle.seed(5)
        net = nn.Linear(4, 2)
        model = paddle.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        model.prepare(optimizer=opt,
                      loss=lambda out, y: ((out - y) ** 2).mean())
        rs = np.random.RandomState(0)
        data = [(rs.randn(4).astype(np.float32),
                 rs.randn(2).astype(np.float32)) for _ in range(3)]
        model.fit(data, epochs=1, batch_size=1, verbose=0, shuffle=False)
        assert tl.counters()["steps_completed"] == 3
        assert validate_timeline(tl) == []


class TestCompileLedger:
    def test_records_and_catches_shape_churn(self):
        paddle.seed(3)
        net = nn.Linear(4, 4)

        @paddle.jit.to_static
        def fwd(x):
            return net(x)

        ledger = CompileLedger()
        with ledger:
            fwd(paddle.to_tensor(np.zeros((2, 4), np.float32)))
            assert ledger.compiles == 1
            rec = ledger.records[0]
            assert rec["arg_specs"] == "float32[2,4]"
            assert rec["seconds"] > 0
            # the miss is attributed to THIS file, not the framework
            assert "test_train_obs.py" in rec["site"]
            assert not rec["steady_state"]
            # steady state: the warmed shape is a hit, not a record
            fwd(paddle.to_tensor(np.zeros((2, 4), np.float32)))
            assert ledger.compiles == 1
            ledger.mark_steady()
            fwd(paddle.to_tensor(np.ones((2, 4), np.float32)))
            assert ledger.steady_state_misses == 0
            # deliberately churn the shape: a NAMED anomaly
            fwd(paddle.to_tensor(np.zeros((3, 4), np.float32)))
            assert ledger.steady_state_misses == 1
            anomalies = ledger.anomalies()
            assert len(anomalies) == 1
            assert anomalies[0]["arg_specs"] == "float32[3,4]"
        # detached: further compiles are not recorded
        fwd(paddle.to_tensor(np.zeros((5, 4), np.float32)))
        assert ledger.compiles == 2
        st = ledger.stats()
        assert st["compiles"] == 2 and st["steady_state_misses"] == 1
        fn_keys = [k for k in st["by_function"] if "fwd" in k]
        assert len(fn_keys) == 1
        assert st["by_function"][fn_keys[0]]["count"] == 2
        assert st["total_seconds"] > 0

    def test_loop_marks_steady_and_stays_flat(self, tmp_path):
        """A fixed-shape resilient-loop run compiles exactly once,
        before steady state; the rollback replay adds nothing."""
        ledger = CompileLedger()
        loop, sentry, train_step = _run_nan_drill(
            tmp_path, NULL_TIMELINE, compile_ledger=ledger)
        assert sentry.rollbacks == 1          # the replay really ran
        assert ledger.compiles == 1
        assert ledger.steady_state_misses == 0
        assert ledger.stats()["compiles"] == 1


class TestCostLedger:
    @pytest.fixture(scope="class")
    def tiny_gpt_step(self):
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny

        paddle.seed(0)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())

        @paddle.jit.to_static
        def train_step(x, y):
            loss = model.compute_loss(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        B, S = 2, 32
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (B, S)))
        y = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (B, S)))
        n_params = sum(int(np.prod(p.shape))
                       for p in model.parameters())
        return train_step, x, y, B * S, n_params

    def test_flops_within_tolerance_of_6nd(self, tiny_gpt_step):
        """XLA's flop count vs the scaling-literature 6ND analytic
        count.  At 345M the ratio is 1.04 (PERF_FINGERPRINT.json); at
        gpt_tiny scale attention + the vocab CE dominate the tiny 6N
        term, so the band is wider but still pins the order of
        magnitude — a broken cost analysis (0, or double-counted
        backward) lands far outside it."""
        train_step, x, y, tokens, n_params = tiny_gpt_step
        ledger = CostLedger(chip=CHIP)
        rec = ledger.add("train_step", train_step, x, y,
                         tokens_per_step=tokens, n_params=n_params)
        assert rec["flops"] > 0
        assert 1.0 <= rec["flops_vs_6nd"] <= 4.0
        assert rec["bytes_accessed"] > 0
        assert rec["hlo_counts"]["dot"] > 0
        assert rec["hlo_counts"]["all_gather"] == 0

    def test_analytic_roofline_and_fingerprint_stable(self,
                                                      tiny_gpt_step):
        train_step, x, y, tokens, n_params = tiny_gpt_step
        ledger = CostLedger(chip=CHIP)
        r1 = ledger.add("train_step", train_step, x, y)
        r2 = ledger.add("train_step", train_step, x, y)
        # identical program, identical analysis → identical fingerprint
        assert r1["fingerprint"] == r2["fingerprint"]
        assert 0.0 < r1["analytic_mfu"] <= 1.0
        assert r1["arithmetic_intensity"] > 0
        assert r1["bound"] in ("compute", "memory")
        # roofline consistency: step time = max of the two components
        peaks = chip_peaks(CHIP)
        peak, bw = peaks.bf16_flops_per_s, peaks.hbm_bytes_per_s
        t_c = r1["flops"] / peak
        t_m = r1["bytes_accessed"] / bw
        assert r1["roofline_step_ms"] == pytest.approx(
            max(t_c, t_m) * 1e3, rel=1e-3)
        st = ledger.stats()
        assert st["analytic_mfu"] == r1["analytic_mfu"]
        json.dumps(st)

    def test_fingerprint_discriminates(self, tiny_gpt_step):
        """A different program (different shape) must move the
        schedule fingerprint — otherwise it can't catch a schedule
        regression either."""
        train_step, x, y, _, _ = tiny_gpt_step
        ledger = CostLedger(chip=CHIP)
        r1 = ledger.add("a", train_step, x, y)
        x2 = paddle.to_tensor(np.asarray(x.numpy())[:1])
        y2 = paddle.to_tensor(np.asarray(y.numpy())[:1])
        r2 = ledger.add("b", train_step, x2, y2)
        assert r1["fingerprint"] != r2["fingerprint"]

    def test_hlo_helpers(self):
        hlo = ("ENTRY %e {\n"
               "  %a = f32[2,2] dot(%x, %y)\n"
               "  %b = f32[2,2] fusion(%a)\n"
               "  ROOT %c = f32[2,2] all-gather(%b)\n"
               "}\n")
        counts = count_hlo_ops(hlo)
        assert counts["dot"] == 1 and counts["fusion"] == 1
        assert counts["all_gather"] == 1
        assert schedule_fingerprint(hlo) == schedule_fingerprint(hlo)
        # reordering moves the fingerprint
        hlo2 = hlo.replace("dot", "zot")
        assert schedule_fingerprint(hlo) != schedule_fingerprint(hlo2)

    def test_unknown_chip_rejected(self):
        with pytest.raises(ValueError):
            chip_peaks("TPU v99")
        with pytest.raises(ValueError):
            CostLedger(chip="cpu")


class TestZeroCompileKeys:
    def test_observatory_adds_zero_cache_keys(self, tmp_path):
        """THE house invariant: attaching the whole observatory —
        timeline, compile ledger, and two cost analyses — to a warmed
        to_static step adds ZERO executable-cache keys."""
        net, opt, sentry, train_step = _rig(seed=11)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        train_step(x)                          # warm
        keys = set(train_step.program_cache.keys())
        assert len(keys) == 1

        tl = StepTimeline()
        ledger = CompileLedger().attach()
        try:
            tl.begin_step(0)
            with tl.phase("step_dispatch"):
                train_step(x)
            tl.end_step()
            cost = CostLedger(chip=CHIP)
            cost.add("step", train_step, x)
            cost.add("step", train_step, x)
        finally:
            ledger.detach()
        assert set(train_step.program_cache.keys()) == keys
        assert ledger.compiles == 0            # observed zero misses
        assert validate_timeline(tl) == []


class TestStatsAndMetrics:
    def test_train_stats_and_exposition(self, tmp_path):
        tl = StepTimeline()
        ledger = CompileLedger()
        cost = CostLedger(chip=CHIP)
        loop, sentry, train_step = _run_nan_drill(
            tmp_path, tl, compile_ledger=ledger, cost_ledger=cost)
        # analyze the drill's warmed program into the loop's cost
        # ledger (the post-warmup step a real driver would take)
        cost.add("train_step", train_step,
                 paddle.to_tensor(np.ones((4, 8), np.float32)))
        snap = loop.train_stats()
        assert snap["timeline"]["steps_completed"] == 8
        assert snap["compiles"]["compiles"] == 1
        assert snap["sentry"]["rollbacks"] == 1
        assert snap["cost"]["analytic_mfu"] > 0
        # profiler aggregation holds the live loop
        stats = profiler.train_stats()
        assert any(s.get("sentry", {}).get("rollbacks") == 1
                   for s in stats.values())
        # one exposition covers both stacks: timeline counters, compile
        # ledger, COST ledger (incl. the fingerprint/chip info gauges),
        # and sentry counters all render under the training prefix
        text = obs.render_all_metrics()
        assert "paddle_tpu_train_timeline_steps_completed" in text
        assert "paddle_tpu_train_compiles_compiles" in text
        assert "paddle_tpu_train_sentry_rollbacks" in text
        assert "paddle_tpu_train_cost_analytic_mfu" in text
        assert "paddle_tpu_train_cost_fingerprint_info" in text
        assert 'chip_info{' in text


class TestStepAblationOffline:
    def test_offline_proxy_smoke(self):
        """tools/step_ablation.py is importable and its offline mode
        decomposes the tiny bench step by cost analysis — fwd_bwd must
        NOT be forward-only (the DCE hazard the cost path caught: a
        cleared grad made the whole backward dead code)."""
        import sys

        sys.path.insert(0, "tools")
        try:
            import step_ablation
        finally:
            sys.path.remove("tools")
        res = step_ablation.offline_ablation(smoke=True, batch=2)
        v = res["variants"]
        assert set(v) == {"full", "fwd_bwd", "fwd"}
        for name, rec in v.items():
            assert rec["flops"] > 0 and rec["bytes_accessed"] > 0, name
            assert 0 < rec["analytic_mfu"] <= 1.0
        # backward is real work: the DCE regression would zero this
        assert res["deltas"]["bwd_flops"] > 0.5 * v["fwd"]["flops"]
        # optimizer is bandwidth, not flops: bytes delta dominates
        assert res["deltas"]["opt_bytes"] > 0
        assert res["fingerprint"]
        json.dumps(res)

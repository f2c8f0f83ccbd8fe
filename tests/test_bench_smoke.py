"""bench.py contract: the smoke path produces the one-line JSON on CPU.
(What bench.py does about the platform it finds is pinned in
tests/test_chip_smoke.py, next to the check it shares with the smoke.)"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_smoke_mode_emits_json_line():
    env = dict(os.environ)
    env["PADDLE_TPU_BENCH_SMOKE"] = "1"
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "gpt2_345m_train_tokens_per_sec_per_chip"
    assert out["value"] > 0
    assert "vs_baseline" in out
    # divergence-sentry rollback drill (ISSUE 12): the injected NaN was
    # detected in-graph, rolled back from the memory snapshot ring
    # (measured restore time), and the window skipped — bench.py exits
    # nonzero unless the recovery actually ran; these assertions pin
    # the fields onto the one-JSON-line contract
    assert out["train_rollback_recovery_ms"] > 0
    assert out["train_sentry_anomalies"] >= 1
    assert out["train_sentry_rollbacks"] >= 1
    assert out["train_sentry_skipped_steps"] >= 1
    # training step observatory (ISSUE 13): the compile ledger saw the
    # bench's own compile (and the steady-state window added zero —
    # bench.py exits nonzero otherwise), the cost ledger produced an
    # analytic roofline MFU + a schedule fingerprint stable across two
    # identical analyses, and the rollback drill's step timeline
    # chain-validated with the rollback span present in the Perfetto
    # export
    assert out["train_compile_count"] >= 1
    assert out["train_compile_seconds"] > 0
    assert 0 < out["train_analytic_mfu"] <= 1.0
    assert out["train_arith_intensity"] > 0
    assert out["train_flops_vs_6nd"] > 0
    assert len(out["train_schedule_fingerprint"]) == 16
    assert out["train_step_trace_valid"] == 1.0
    assert out["train_step_trace_events"] > 0
    # compute/collective overlap (ISSUE 16): the drill compiled the
    # chunks=1 and chunked TP=4 schedules side by side and bench.py
    # exits nonzero unless the overlapped program has STRICTLY fewer
    # exposed collectives at f32 loss parity with a stable fingerprint
    # and zero new executable-cache keys; the pinned fields put the
    # exposure count and overlapped-schedule fingerprint on the
    # one-JSON-line contract
    assert out["train_tp_overlap_enabled"] == 1.0
    assert out["train_tp_overlap_exposed_collectives"] > 0
    assert len(out["train_tp_overlap_fingerprint"]) == 16
    # elastic reconfiguration drill (ISSUE 17): the dp=4 → dp=2 resume
    # actually resharded (bench.py exits nonzero unless the resharded
    # state is bitwise identical to the committed generation, zero
    # samples of the elastic schedule were lost or duplicated across
    # the world change, and the post-resume steady state added zero
    # compiles); the pinned fields put the reconfiguration price and
    # the exactly-once audit on the one-JSON-line contract
    assert out["train_elastic_reconfig_ms"] > 0
    assert out["train_elastic_replayed_steps"] >= 1
    assert out["train_elastic_lost_samples"] == 0


@pytest.mark.slow
def test_serving_mode_emits_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_BENCH_MODE"] = "serving"
    r = subprocess.run([sys.executable, "bench.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "serving_gpt_tiny_decode_tokens_per_sec"
    assert out["value"] > 0
    assert out["ttft_ms"] > 0
    assert out["compile_misses"] > 0  # warmup compiles; steady state adds 0
    # resilience counters ride along and are all zero on the smoke path
    for k in ("requests_failed", "requests_cancelled", "requests_rejected",
              "deadline_expired", "step_retries"):
        assert out[k] == 0, (k, out)
    assert out["engine_state"] == "active"
    # sync-point sanitizer (ISSUE 7 baseline: 1.0 — the host-side
    # sampling logits pull).  ISSUE 11 moved sampling on-device: the
    # decode dispatch performs ZERO blocking host transfers, measured
    # with the sanitizer armed.  Any other value means a sync crept
    # back into the decode hot path
    assert out["serving_decode_host_transfers"] == 0.0, out
    # paged-kernel vs reference-gather decode microbench (ISSUE 11):
    # both paths ran at zero steady-state misses with bitwise-equal
    # greedy outputs (bench exits nonzero otherwise); the speedup ratio
    # is the tracked trajectory — in CPU interpret mode the Pallas
    # kernel pays an interpreter tax, so only positivity is pinned
    # here (>= 1 is the on-TPU expectation, where the kernel also skips
    # the materialized contiguous K/V gather)
    assert out["serving_paged_kernel_tokens_per_sec"] > 0
    assert out["serving_paged_reference_tokens_per_sec"] > 0
    assert out["serving_paged_kernel_speedup"] > 0
    # speculative decoding drill (ISSUE 15): greedy bitwise vs the
    # non-speculative run and zero steady-state misses in both modes
    # are enforced by bench.py (nonzero exit otherwise); the pinned
    # fields say the acceptance machinery actually fired and both
    # throughput numbers ride the one-JSON-line contract (the tokens/
    # sec PAIR is the trajectory — no ordering is pinned on CPU, where
    # a random-weight draft prices pure overhead)
    assert out["serving_spec_accept_rate"] > 0
    assert out["serving_spec_tokens_per_round"] >= 1.0
    assert out["serving_spec_tokens_per_sec"] > 0
    assert out["serving_nospec_tokens_per_sec"] > 0
    # paged KV + prefix reuse (ISSUE 5): the shared-prefix workload must
    # actually hit the cache
    assert out["serving_prefix_hit_rate"] > 0
    assert out["serving_kv_blocks_in_use"] > 0
    assert out["ttft_ms_paged"] > 0 and "ttft_ms_contiguous" not in out
    assert out["paged_engine_state"] == "active"
    # fleet failover smoke (ISSUE 6): the scripted replica kill must have
    # actually happened (>= 1 redispatch), the fleet must have healed
    # (measured recovery time), and throughput stays positive across it
    assert out["serving_fleet_tokens_per_sec"] > 0
    assert out["serving_fleet_failover_recovery_ms"] > 0
    assert out["serving_fleet_redispatches"] >= 1
    # overload trace-replay (ISSUE 8): p50/p99 TTFT and ITL under a
    # seeded Poisson overload, the preemption/shed counters actually
    # fired, and priority scheduling beat the no-priority baseline on
    # the identical trace (bench.py exits nonzero otherwise — these
    # assertions pin the fields onto the one-JSON-line contract)
    assert out["serving_ttft_p50_ms"] > 0
    assert out["serving_ttft_p99_ms"] >= out["serving_ttft_p50_ms"]
    assert out["serving_itl_p50_ms"] > 0
    assert out["serving_itl_p99_ms"] >= out["serving_itl_p50_ms"]
    assert out["serving_preemptions"] >= 1
    assert out["serving_shed"] >= 1
    assert out["serving_high_ttft_p99_ms"] < \
        out["serving_baseline_high_ttft_p99_ms"]
    # request-lifecycle tracing (ISSUE 9): the measured trace-replay run
    # recorded a span chain, the chain validator passed (1.0 = every
    # request terminal exactly once with preempt links intact and a
    # well-formed Perfetto export), and — via the zero-compile-miss
    # gates above — the traced run added no steady-state compiles
    assert out["serving_trace_events"] > 0
    assert out["serving_trace_valid"] == 1.0
    # durability drills (ISSUE 14): the crash-recovery drill replayed
    # real in-flight work from the journal and finished it (bench fails
    # structured on any lost request, duplicate terminal, or recovery
    # compile miss), and the rolling hot-swap completed at version 1
    # with the worst per-request inter-token gap measured across the
    # roll (>= 0; stall-free is legal, lost traffic is not)
    assert out["serving_recovery_ms"] > 0
    assert out["serving_journal_replayed"] >= 1
    assert out["serving_hot_swap_stall_ms"] >= 0
    assert out["serving_hot_swap_roll_ms"] > 0
    assert out["serving_hot_swap_model_version"] == 1
    # tensor-parallel sharded serving (ISSUE 18): the 2-shard drill ran
    # on the virtual mesh with greedy outputs bitwise equal to the
    # single-chip engine at zero steady-state recompiles (bench fails
    # structured otherwise); the throughput and its ratio to the
    # single-chip baseline ride the one-JSON-line contract (the ratio
    # prices the per-layer TP all-reduces — no ordering pinned on CPU,
    # where two host devices emulate one chip each)
    assert out["serving_sharded_tokens_per_sec"] > 0
    assert out["serving_sharded_mesh_shape"] == "model=2"
    assert out["serving_sharded_vs_single_chip"] > 0
    # degraded-mode serving (ISSUE 19): the kill-a-shard drill SIGKILLed
    # a model=2 serving process mid-decode, rebuilt the group at the
    # largest viable mp' on the survivor, and replayed the journal
    # cross-mesh (bench fails structured on any lost request, output
    # divergence from the uninterrupted oracle, steady-state recompile,
    # or duplicate terminal) — mp' is 1 on the 1-survivor drill and
    # nothing may be lost, ever
    assert out["serving_degraded_rebuild_ms"] > 0
    assert out["serving_degraded_mp"] == 1
    assert out["serving_degraded_replayed"] >= 1
    assert out["serving_degraded_lost"] == 0
    # multi-tenant serving (ISSUE 20): one paged engine served a
    # heterogeneous Poisson mix of base / two LoRA adapters / JSON-
    # grammar tenants through the SAME warmed executables — bench fails
    # structured on any steady-state compile miss, any cross-tenant
    # prefix hit, or any invalid grammar output, so the pinned fields
    # put per-class TTFT, the swap latency, and the validity rate on
    # the one-JSON-line contract
    assert out["serving_grammar_valid_rate"] == 1.0
    assert out["serving_adapter_swap_ms"] > 0
    for cls in ("base", "lora_a", "lora_b", "json"):
        assert out[f"serving_tenant_{cls}_ttft_p50_ms"] > 0
        assert out[f"serving_tenant_{cls}_ttft_p99_ms"] >= \
            out[f"serving_tenant_{cls}_ttft_p50_ms"]

"""Offline perf-regression fingerprint of the bench train step.

Compiles (without running) the EXACT program bench.py times and records
structural facts a perf regression would move: total FLOPs, bytes
accessed, memory-analysis peaks, and the optimized-HLO op mix (dot /
fusion / custom-call / collective counts).  The tracked artifact
PERF_FINGERPRINT.json is asserted by tests/test_perf_fingerprint.py, so
the compiled program cannot silently rot between chip runs (reference
analog: tools/check_op_benchmark_result.py:70 — the reference gates op
perf PR-vs-develop; this is the hardware-free equivalent over
compiled-program structure).

CPU lowering note: XLA:CPU sees the same jaxpr → same FLOPs, dot shapes
and collective structure as TPU; it does NOT capture Pallas custom
kernels (flash attention falls back to the XLA path off-TPU), so the
custom-call count here tracks host callbacks only.

Usage:
  python tools/perf_fingerprint.py            # smoke config, update file
  python tools/perf_fingerprint.py --full     # + the 345M/1024 config
  python tools/perf_fingerprint.py --check    # compare, exit 1 on drift
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ARTIFACT = os.path.join(REPO, "PERF_FINGERPRINT.json")

# a CPU-only tool: pin the platform before any backend initialization so
# it never takes the chip, whatever the caller's environment says
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# HLO op counting is shared with the runtime cost ledger (ISSUE 13:
# paddle_tpu/obs/hlo_cost.py generalizes this tool's one-shot logic into
# the per-executable CostLedger) — importing it here means the tracked
# artifact and the ledger can never count ops differently
from paddle_tpu.obs.hlo_cost import count_hlo_ops as _count_ops  # noqa: E402
from paddle_tpu.obs.hlo_cost import schedule_fingerprint  # noqa: E402


def fingerprint(smoke: bool, batch: int) -> dict:
    """Compile (not run) the bench train step and extract its structure.
    `smoke` flows to bench.build_bench directly — the
    PADDLE_TPU_BENCH_SMOKE env var only matters to bench.main()."""
    os.environ.setdefault("PADDLE_TPU_BENCH_AMP", "O2")
    import bench

    make_step, cfg, seq, model = bench.build_bench(smoke=smoke)
    train_step, x, y = make_step(batch)
    prog = train_step.get_concrete_program(x, y)
    # compiled_stats lowers+compiles the donating program without
    # executing it — no 345M forward ever runs on the CPU here
    prog._last_arg_arrays = [x._value(), y._value()]
    stats = prog.compiled_stats()   # one lower+compile: hlo+memory+cost
    hlo = stats.pop("hlo")
    counts = _count_ops(hlo)
    cost = stats.pop("cost", {})

    import numpy as np

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return {
        "config": {
            "smoke": smoke, "batch": batch, "seq": seq,
            "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
            "vocab": cfg.vocab_size,
            "amp": os.environ.get("PADDLE_TPU_BENCH_AMP", "O2"),
        },
        "n_params": n_params,
        "cost": cost,
        "hlo_counts": counts,
        # opcode-sequence digest (obs.hlo_cost): the schedule surface
        # the compute/collective-overlap work will be asserted on
        "schedule_fingerprint": schedule_fingerprint(hlo),
        "memory": {k: v for k, v in stats.items()},
        "jax_version": jax.__version__,
    }


# drift tolerances per field class: flops are a pure function of the
# traced program (tight); fusion decisions may wiggle with minor XLA
# heuristics (loose); collective/dot structure must not move at all
_TOLERANCES = {
    "cost.flops": 0.01,
    "cost.bytes_accessed": 0.10,
    "memory.peak_bytes": 0.10,
    "memory.temp_bytes": 0.15,
    "hlo_counts.fusion": 0.15,
    "hlo_counts.while": 0.0,
    "hlo_counts.dot": 0.0,
    "hlo_counts.custom_call": 0.0,
    "hlo_counts.convolution": 0.0,
    "hlo_counts.all_reduce": 0.0,
    "hlo_counts.all_gather": 0.0,
    "hlo_counts.reduce_scatter": 0.0,
    "hlo_counts.collective_permute": 0.0,
    "hlo_counts.all_to_all": 0.0,
}


def compare(tracked: dict, current: dict) -> list:
    """Returns a list of human-readable drift messages (empty = clean)."""
    if tracked.get("jax_version") != current.get("jax_version"):
        return [f"jax version changed "
                f"({tracked.get('jax_version')} -> "
                f"{current.get('jax_version')}): fingerprint must be "
                "regenerated, not compared"]
    msgs = []
    for path, tol in _TOLERANCES.items():
        sect, key = path.split(".")
        a = tracked.get(sect, {}).get(key)
        b = current.get(sect, {}).get(key)
        if a is None or b is None:
            continue
        if a == b:
            continue
        denom = max(abs(a), 1e-9)
        rel = abs(a - b) / denom
        if rel > tol:
            msgs.append(
                f"{path}: tracked {a} vs current {b} "
                f"(rel {rel:.3f} > tol {tol})")
    return msgs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="also fingerprint the 345M/1024 bench config "
                         "(minutes of XLA CPU compile)")
    ap.add_argument("--check", action="store_true",
                    help="compare against the tracked artifact instead "
                         "of rewriting it")
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args()

    tracked = {}
    if os.path.exists(ARTIFACT):
        with open(ARTIFACT) as f:
            tracked = json.load(f)

    results = dict(tracked)
    drift = []
    configs = [("smoke", True, args.batch or 2)]
    if args.full:
        configs.append(("full", False, args.batch or 8))
    for name, smoke, batch in configs:
        cur = fingerprint(smoke=smoke, batch=batch)
        if args.check and name in tracked:
            drift += [f"[{name}] {m}" for m in compare(tracked[name], cur)]
        results[name] = cur

    if args.check:
        if drift:
            print("PERF FINGERPRINT DRIFT:")
            for m in drift:
                print(" ", m)
            sys.exit(1)
        print("fingerprint clean")
        return
    with open(ARTIFACT, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {ARTIFACT}")
    for name in results:
        c = results[name]
        print(f"  {name}: flops={c['cost'].get('flops')} "
              f"counts={c['hlo_counts']}")


if __name__ == "__main__":
    main()

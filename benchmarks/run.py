"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It refuses anything that is not a TPU in
the benchmark's own peaks table, keeps JAX's persistent compilation cache at
``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.xla_cache``, loads the cell's
configuration, mix, driver and metric readers by name, runs the driver and
prints the contract's one JSON object as the last line of standard output.
Everything else worth reading goes on earlier lines.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest as mf            # noqa: E402
from benchmarks.harness import trace_reduce              # noqa: E402
from benchmarks.harness.context import RunContext        # noqa: E402


def place_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), else the
    fixed ``<checkout>/.xla_cache``; every program is cached, however fast
    it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".xla_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def read_metrics(cell, result: dict, ctx) -> dict:
    """Per-layer metrics of a traced run: one reader file per metric, found
    by the metric's name; a reader that finds nothing returns ``None`` and
    the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = mf.load_module("metrics", m["name"])
        value = reader.read(result, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control (never set "
                         "by the benchmark's own runs)")
    args = ap.parse_args(argv)
    cell = mf.Cell(mf.load_manifest(), args.workload)

    from benchmarks.harness import peaks as pk
    import jax

    device, peaks = pk.attached(cell.chips)    # refuses before anything
    cache = place_compile_cache()
    out_dir = os.path.join(ROOT, ".bench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = RunContext(config=cell.config, mix=cell.mix, limits=cell.limits,
                     seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     chips=cell.chips, peaks=peaks, out_dir=out_dir,
                     t_start=T_START, control=bool(args.control))
    ctx.say(f"cell {cell.name} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace} device {device} jax {jax.__version__} "
            f"compile cache {cache}")
    driver = mf.load_module("drivers", cell.mix["kind"])
    result = driver.run(ctx)
    e2e = result["end_to_end"]
    device["memory_peak_bytes"] = int(
        result.get("memory_peak_bytes") or pk.memory_peak_bytes())
    ctx.say("end_to_end " + json.dumps(e2e))
    ctx.say("facts " + json.dumps(result["facts"], default=str))
    line = {"correct": result["checks"].correct,
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": device}
    if args.trace:
        trace = result["trace"]
        if trace is None or not trace.device_ops:
            raise SystemExit("benchmark: the traced run holds no device "
                             "operation")
        t0, t1 = trace.window()
        device["busy_s"] = trace_reduce.busy_seconds(trace)
        device["window_s"] = t1 - t0
        with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
            json.dump(trace_reduce.summary(trace), f, indent=1)
        line["metrics"] = read_metrics(cell, result, ctx)
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        line["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

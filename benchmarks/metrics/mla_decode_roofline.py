"""The latent decode kernel's share of its roofline in the traced slice: the
least time the chip could take for the live latent vectors each call has to
read (or its operations, whichever bounds) over the kernel's summed device
time.

The live tokens of each step come from the benchmark's ``engine.step`` span
(``kv_tokens`` at the step's start, written into the profiler trace as the
annotation's argument); a step launches the kernel once per layer.  Prompts
admitted inside a step are not in its ``kv_tokens``, so the share errs low,
never high.  A program without the kernel (no event of that name) gives
nothing to read."""
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or "kv_rank" not in d:
        return None
    kc = load_module("kernel_costs", "mla_paged_decode")
    seconds, n_events = trace_reduce.kernel_seconds(trace, kc.PATTERNS)
    steps = [a for name, _s, _e, a in trace.host_spans
             if name == "engine.step" and a.get("running", 0) > 0]
    if not n_events or not steps:
        return None
    # whole steps' worth of launches seen; the slice's edges cut a step
    seen = n_events / float(d["layers"])
    kv = sum(a.get("kv_tokens", 0) for a in steps) * min(
        1.0, seen / len(steps))
    flops, nbytes = kc.cost(kv, heads=d["heads"],
                            width=d["kv_rank"] + d["rope"], dv=d["kv_rank"],
                            itemsize=f["kv_itemsize"])
    flops, nbytes = flops * d["layers"], nbytes * d["layers"]
    by_ops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"mla_decode_roofline: {n_events} kernel events over "
            f"{len(steps)} decode steps, {seconds:.4f}s on the device; least "
            f"time by operations {by_ops:.5f}s, by bytes {by_bytes:.5f}s -> "
            f"bound by {'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds

"""The grouped expert kernel's share of its roofline over the decode steps
of the traced slice: the least time the chip could take to read the weights
of the held experts that got a token, plus the assignments' rows (or for the
operations, whichever bounds), over the kernel's summed device time in those
steps.

Decode calls are told from prefill calls by their rows (``slots * top_k``).
What each step routed comes from the program's own ``engine.step`` spans
(``moe_assignments_held``, ``moe_experts_touched``: counted by the decode
program, pulled with the tokens), taken from the ring and laid over the
trace through ``program_spans.clock_offset``.  A step sums its expert layers,
so the cost is computed a step at a time with the step's mean per layer; the
slice's edges cut steps, so the steps' sum is scaled to the launches seen.
A program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    rows = ps.rows()
    if trace is None or ctx.peaks is None or "moe_ffn" not in d or not rows:
        return None
    kc = load_module("kernel_costs", "moe_grouped_matmul")
    seconds, n_events = trace_reduce.kernel_seconds(
        trace, kc.patterns_for_rows(f["num_slots"] * d["top_k"]))
    offset = ps.clock_offset(result, ctx.say)
    if not n_events or offset is None:
        return None
    t0, t1 = trace.window()
    steps = [r[ps.ATTRS] for r in ps.named(rows, "engine.step",
                                           t0 - offset, t1 - offset)
             if "moe_experts_touched" in r[ps.ATTRS]]
    if not steps:
        return None
    layers = d["layers"] - d["dense_layers"]
    flops = nbytes = 0.0
    for a in steps:
        fl, nb = kc.cost(a["moe_assignments_held"] / layers,
                         a["moe_experts_touched"] / layers,
                         hidden=d["hidden"], ffn=d["moe_ffn"],
                         itemsize=f["kv_itemsize"])
        flops, nbytes = flops + fl * layers, nbytes + nb * layers
    # two launches a layer a step; the slice's edges cut steps
    scale = min(1.0, n_events / (2.0 * layers) / len(steps))
    by_ops = scale * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = scale * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"moe_experts_roofline: {n_events} decode-time kernel events, "
            f"{len(steps)} whole decode steps in the slice, {seconds:.4f}s on "
            f"the device; least time by operations {by_ops:.5f}s, by bytes "
            f"{by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds

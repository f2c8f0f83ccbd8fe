"""The index-scores kernel's share of its roofline over the decode steps of
the traced slice: the least time the chip could take to read the running
slots' cached indexer keys at their stored width (or for the operations,
whichever bounds) over ``dsa_index_scores``'s summed device time in the decode
program (its calls there score one query a slot; the prefill program's score
a group of rows and are left out by their shape).

What each step had cached comes from the program's own ``engine.step`` spans
(``dsa_context``, counted by the decode program and pulled with the tokens),
taken from the ring and laid over the trace through
``program_spans.clock_offset``.  A step launches the kernel once a layer; the
slice's edges cut steps, so the steps' sum is scaled to the launches seen.  A
program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def share(result, ctx, name: str, kernel: str, cost_of_step):
    """``100 * least time / device time`` of ``kernel`` over the slice's
    decode steps; ``cost_of_step(kc, attrs, dims, facts)`` gives one layer's
    ``(flops, bytes)`` for a step."""
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or "idx_heads" not in d:
        return None
    kc = load_module("kernel_costs", kernel)
    seconds, n_events = trace_reduce.kernel_seconds(trace, kc.PATTERNS)
    offset = ps.clock_offset(result, ctx.say) if n_events else None
    if offset is None:
        return None
    t0, t1 = trace.window()
    steps = [a for a in load_module("metrics", "dsa_selected_share").steps(
        result, t0 - offset, t1 - offset)
        if a["dsa_selected"] < a["dsa_context"]]     # the indexed path ran
    if not steps:
        return None
    flops = nbytes = 0.0
    for a in steps:
        fl, nb = cost_of_step(kc, a, d, f)
        flops, nbytes = flops + fl * d["layers"], nbytes + nb * d["layers"]
    # one launch a layer a step; the slice's edges cut steps
    scale = min(1.0, n_events / float(d["layers"]) / len(steps))
    by_ops = scale * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = scale * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"{name}: {n_events} decode-time kernel events, {len(steps)} "
            f"whole decode steps in the slice, {seconds:.4f}s on the device "
            f"({1e6 * seconds / n_events:.1f} us a call); least time by "
            f"operations {by_ops:.5f}s, by bytes {by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds


def read(result, ctx):
    return share(
        result, ctx, "dsa_index_roofline", "dsa_index_scores",
        lambda kc, a, d, f: kc.cost(
            a["dsa_context"], heads=d["idx_heads"], width=d["idx_dim"],
            stored_width=-(-d["idx_dim"] // 128) * 128,
            itemsize=f["kv_itemsize"]))

"""The paged decode kernel's share of its roofline in a stack where only some
layers are attention, over the decode steps of the traced slice: the least
time the chip could take to read the keys and values the **attention** layers'
calls must read — ``dims["attn_layers"]`` times the running slots' lengths,
by ``kernel_costs/paged_decode.py`` as it is — over
``paged_decode_attention``'s summed device time.

What each step's slots hold comes from the program's own ``engine.step`` spans
(``swa_full_rows``: every running slot's tokens and the new one), laid over
the trace through ``program_spans.clock_offset``.  A step launches the kernel
once an attention layer; the slice's edges cut steps, so the steps' sum is
scaled to the launches seen.  (``paged_decode_roofline`` charges every layer
of ``dims["layers"]`` the whole context.)  A program without the kernel, the
attributes or ``attn_layers`` gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or not d.get("attn_layers"):
        return None
    seconds, n_events = trace_reduce.kernel_seconds(
        trace, load_module("kernel_costs", "swa_paged_decode").PATTERNS)
    offset = ps.clock_offset(result, ctx.say) if n_events else None
    if offset is None:
        return None
    t0, t1 = trace.window()
    steps = load_module("metrics", "swa_attended_share").steps(
        result, t0 - offset, t1 - offset)
    if not steps:
        return None
    layers = d["attn_layers"]
    flops, nbytes = load_module("kernel_costs", "paged_decode").cost(
        sum(a["swa_full_rows"] for a in steps), heads=d["heads"],
        kv_heads=d["kv_heads"], head_dim=d["head_dim"],
        itemsize=f["kv_itemsize"])
    # one launch an attention layer a step; the slice's edges cut steps
    scale = layers * min(1.0, n_events / float(layers) / len(steps))
    by_ops = scale * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = scale * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"hybrid_decode_roofline: {n_events} kernel events, {len(steps)} "
            f"whole decode steps in the slice, {layers} attention layers of "
            f"{d['layers']}, {seconds:.4f}s on the device "
            f"({1e6 * seconds / n_events:.1f} us a call); least time by "
            f"operations {by_ops:.5f}s, by bytes {by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds

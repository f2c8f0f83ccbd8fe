"""The windowed decode kernel's share of its roofline over the decode steps
of the traced slice: the least time the chip could take to read the K and V
of the rows the running slots attend to — the exact positions of their own
window and the summary rows of the windows passed — (or for the operations,
whichever bounds) over ``eva_paged_decode``'s summed device time.

What each step attended to comes from the program's own ``engine.step`` spans
(``eva_exact_rows``, ``eva_summary_rows``, counted by the decode program and
pulled with the tokens), taken from the ring and laid over the trace through
``program_spans.clock_offset``.  A step launches the kernel once a layer; the
slice's edges cut steps, so the steps' sum is scaled to the launches seen.  A
program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def share(result, ctx, name: str, kernel: str, calls_in, cost_of_call):
    """``100 * least time / device time`` of ``kernel`` over the slice:
    ``calls_in(t0, t1)`` gives the attributes of the program's spans (a
    decode step, a prefill) that launched it once a layer between two
    ``perf_counter`` readings, ``cost_of_call(kc, attrs, dims, facts)`` one
    layer's ``(flops, bytes)``."""
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or "window" not in d:
        return None
    kc = load_module("kernel_costs", kernel)
    seconds, n_events = trace_reduce.kernel_seconds(trace, kc.PATTERNS)
    offset = ps.clock_offset(result, ctx.say) if n_events else None
    if offset is None:
        return None
    t0, t1 = trace.window()
    calls = calls_in(t0 - offset, t1 - offset)
    if not calls:
        return None
    flops = nbytes = 0.0
    for a in calls:
        fl, nb = cost_of_call(kc, a, d, f)
        flops, nbytes = flops + fl * d["layers"], nbytes + nb * d["layers"]
    # one launch a layer a call; the slice's edges cut calls
    scale = min(1.0, n_events / float(d["layers"]) / len(calls))
    by_ops = scale * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = scale * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"{name}: {n_events} kernel events, {len(calls)} whole calls of "
            f"their program in the slice, {seconds:.4f}s on the device "
            f"({1e6 * seconds / n_events:.1f} us a call); least time by "
            f"operations {by_ops:.5f}s, by bytes {by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds


def read(result, ctx):
    steps = load_module("metrics", "eva_attended_share").steps
    return share(
        result, ctx, "eva_decode_roofline", "eva_paged_decode",
        lambda t0, t1: [a for a in steps(result, t0, t1)
                        if a["eva_summary_rows"] > 0],  # the windowed kernel
        lambda kc, a, d, f: kc.cost(
            a["eva_exact_rows"], a["eva_summary_rows"], heads=d["heads"],
            kv_heads=d["kv_heads"], head_dim=d["head_dim"],
            itemsize=f["kv_itemsize"]))

"""The chunked scan kernel's share of its roofline over the tail prefills of
the traced slice: the least time the chip could take for the gated delta rule
over the tails' real tokens (``kernel_costs/kda_chunk_prefill.py``: operations
at the bf16 peak, or the bytes of its operands and states if that is longer)
over ``kda_chunk_prefill``'s summed device time.

What each tail holds comes from the program's own ``engine.prefill`` spans
(``kda_tail_tokens``), laid over the trace through
``program_spans.clock_offset``.  A prefill launches the kernel once a KDA
layer (``dims["state_layers"]``); the slice's edges cut prefills, so the
prefills' sum is scaled to the launches seen.  A program without the kernel,
the attributes or ``state_layers`` gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def share(result, ctx, name: str, kernel: str, span: str, attr: str,
          cost_of_call):
    """``100 * least time / device time`` of ``kernel`` over the slice: the
    program's spans named ``span`` that carry ``attr`` launched it once a
    state layer; ``cost_of_call(kc, attrs, dims)`` is ``(flops, bytes)`` of
    all of a call's launches."""
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or not d.get("state_layers"):
        return None
    kc = load_module("kernel_costs", kernel)
    seconds, n_events = trace_reduce.kernel_seconds(trace, kc.PATTERNS)
    offset = ps.clock_offset(result, ctx.say) if n_events else None
    rows = ps.rows()
    if offset is None or not rows:
        return None
    t0, t1 = trace.window()
    calls = [r[ps.ATTRS] for r in ps.named(rows, span, t0 - offset,
                                           t1 - offset)
             if r[ps.ATTRS].get(attr, 0) > 0]
    if not calls:
        return None
    flops = nbytes = 0.0
    for a in calls:
        fl, nb = cost_of_call(kc, a, d)
        flops, nbytes = flops + fl, nbytes + nb
    # one launch a state layer a call; the slice's edges cut calls
    scale = min(1.0, n_events / float(d["state_layers"]) / len(calls))
    by_ops = scale * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = scale * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"{name}: {n_events} kernel events, {len(calls)} whole calls of "
            f"their program in the slice, {d['state_layers']} state layers, "
            f"{seconds:.4f}s on the device ({1e6 * seconds / n_events:.1f} us "
            f"a call); least time by operations {by_ops:.5f}s, by bytes "
            f"{by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds


def read(result, ctx):
    def cost(kc, a, d):
        fl, nb = kc.cost(a["kda_tail_tokens"], heads=d["kda_heads"],
                         dim=d["kda_dim"])
        return fl * d["state_layers"], nb * d["state_layers"]

    return share(result, ctx, "kda_prefill_roofline", "kda_chunk_prefill",
                 "engine.prefill", "kda_tail_tokens", cost)

"""The latent decode kernel's share of its roofline in a stack where only
some layers are latent attention, over the decode steps of the traced slice:
``mla_decode_roofline``'s reading with the kernel charged
``dims["attn_layers"]`` layers, not every layer of ``dims["layers"]`` (which
in a stack of 3 latent layers of 13 reads 4.3 times too high).

The live tokens of each step come from the benchmark's ``engine.step`` span
(``kv_tokens`` at the step's start); a step launches the kernel once a latent
layer.  Prompts admitted inside a step are not in its ``kv_tokens``, so the
share errs low, never high.  A program without the kernel, ``kv_rank`` or
``attn_layers`` gives nothing to read."""
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    trace, f = result.get("trace"), result["facts"]
    d = f.get("dims") or {}
    if trace is None or ctx.peaks is None or "kv_rank" not in d \
            or not d.get("attn_layers"):
        return None
    kc = load_module("kernel_costs", "mla_paged_decode")
    seconds, n_events = trace_reduce.kernel_seconds(trace, kc.PATTERNS)
    steps = [a for name, _s, _e, a in trace.host_spans
             if name == "engine.step" and a.get("running", 0) > 0]
    if not n_events or not steps:
        return None
    layers = d["attn_layers"]
    # whole steps' worth of launches seen; the slice's edges cut a step
    kv = sum(a.get("kv_tokens", 0) for a in steps) * min(
        1.0, n_events / float(layers) / len(steps))
    flops, nbytes = kc.cost(kv, heads=d["heads"],
                            width=d["kv_rank"] + d["rope"], dv=d["kv_rank"],
                            itemsize=f["kv_itemsize"])
    by_ops = layers * flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = layers * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"hybrid_mla_decode_roofline: {n_events} kernel events over "
            f"{len(steps)} decode steps, {layers} latent layers of "
            f"{d['layers']}, {seconds:.4f}s on the device; least time by "
            f"operations {by_ops:.5f}s, by bytes {by_bytes:.5f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / seconds

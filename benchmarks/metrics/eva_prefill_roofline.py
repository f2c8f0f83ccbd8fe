"""The windowed prefill kernel's share of its roofline over the prefills of
the traced slice: the least time the chip could take for the tails'
attention — each real query row over the exact keys of its own window at or
before it and the summary rows of every earlier window, at the bf16 peak, or
for the bytes of those keys, values and summaries if that is longer — over
``eva_paged_prefill``'s summed device time.

What each tail attended to comes from the program's own ``engine.prefill``
spans (``eva_rows``, ``eva_keys``: a layer's count, by the program's rule on
the host), laid over the trace as ``eva_decode_roofline`` lays its steps (its
``share`` does the work).  A program without the kernel or the attributes
gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    rows = ps.rows()
    return load_module("metrics", "eva_decode_roofline").share(
        result, ctx, "eva_prefill_roofline", "eva_paged_prefill",
        lambda t0, t1: [r[ps.ATTRS]
                        for r in ps.named(rows, "engine.prefill", t0, t1)
                        if r[ps.ATTRS].get("eva_rows", 0) > 0],
        lambda kc, a, d, f: kc.cost(
            a["eva_rows"], a["eva_keys"], heads=d["heads"],
            kv_heads=d["kv_heads"], head_dim=d["head_dim"],
            itemsize=f["kv_itemsize"]))

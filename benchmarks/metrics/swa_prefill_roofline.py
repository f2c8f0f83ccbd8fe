"""The paged tail-prefill kernel's share of its roofline, in a model whose
layers are of two kinds, over the prefills of the traced slice: the least
time the chip could take for the tails' attention — each real query row over
every key at or before it on a full layer, over its window on a window layer,
at the bf16 peak, or for the bytes of the distinct keys and values behind
them if that is longer — over ``paged_prefill_attention``'s summed device
time.

What each tail must read comes from the program's own ``engine.prefill``
spans (``swa_full_rows`` / ``swa_window_rows``, ``swa_full_keys`` /
``swa_window_keys``: by the kernels' rule on the host), laid over the trace
by ``eva_decode_roofline``'s ``share``; the cost is the layers' mean.  A
program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    d = result["facts"].get("dims") or {}
    if "full_layers" not in d:
        return None
    rows = ps.rows()
    mean = load_module("kernel_costs", "swa_paged_decode").mean_rows
    kinds = dict(layers=d["layers"], full_layers=d["full_layers"])
    return load_module("metrics", "eva_decode_roofline").share(
        result, ctx, "swa_prefill_roofline", "swa_paged_prefill",
        lambda t0, t1: [r[ps.ATTRS]
                        for r in ps.named(rows, "engine.prefill", t0, t1)
                        if r[ps.ATTRS].get("swa_full_rows", 0) > 0],
        lambda kc, a, d, f: kc.cost(
            mean(a["swa_full_rows"], a["swa_window_rows"], **kinds),
            mean(a["swa_full_keys"], a["swa_window_keys"], **kinds),
            heads=d["heads"], kv_heads=d["kv_heads"],
            head_dim=d["head_dim"], itemsize=f["kv_itemsize"]))

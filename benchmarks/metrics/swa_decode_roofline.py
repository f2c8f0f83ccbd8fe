"""The paged decode kernel's share of its roofline, in a model whose layers
are of two kinds, over the decode steps of the traced slice: the least time
the chip could take to read the keys and values each call **must** read — a
slot's whole length on a full layer, the window on a window layer — over
``paged_decode_attention``'s summed device time.

What each step's layers read comes from the program's own ``engine.step``
spans (``swa_full_rows``, ``swa_window_rows``), laid over the trace as
``eva_decode_roofline`` lays its steps (its ``share`` does the work: a launch
a layer a step, the slice's edges scaled away; the cost is the layers' mean).
A program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    d = result["facts"].get("dims") or {}
    if "full_layers" not in d:
        return None
    steps = load_module("metrics", "swa_attended_share").steps
    return load_module("metrics", "eva_decode_roofline").share(
        result, ctx, "swa_decode_roofline", "swa_paged_decode",
        lambda t0, t1: steps(result, t0, t1),
        lambda kc, a, d, f: kc.cost(
            a["swa_full_rows"], a["swa_window_rows"], layers=d["layers"],
            full_layers=d["full_layers"], heads=d["heads"],
            kv_heads=d["kv_heads"], head_dim=d["head_dim"],
            itemsize=f["kv_itemsize"]))

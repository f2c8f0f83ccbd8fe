"""The sparse decode kernel's share of its roofline over the decode steps of
the traced slice: the least time the chip could take to read the selected
tokens' K and V rows (or for the operations, whichever bounds) over
``dsa_sparse_decode``'s summed device time.  What each step selected comes
from the program's own ``engine.step`` spans (``dsa_selected``), as
``dsa_index_roofline`` reads ``dsa_context`` (its ``share`` does the work).
A program without the kernel or the attributes gives nothing to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    return load_module("metrics", "dsa_index_roofline").share(
        result, ctx, "dsa_decode_roofline", "dsa_sparse_decode",
        lambda kc, a, d, f: kc.cost(
            a["dsa_selected"], heads=d["heads"], kv_heads=d["kv_heads"],
            head_dim=d["head_dim"], itemsize=f["kv_itemsize"]))

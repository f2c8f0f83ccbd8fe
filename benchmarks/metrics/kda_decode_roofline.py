"""The KDA step kernel's share of its roofline over the decode steps of the
traced slice: the least time the chip could take to read and write the
running slots' recurrent state (``state_bytes`` of the program's own
``engine.step`` spans: running slots x KDA layers x 2 x a slot's state, by
``kernel_costs/kda_decode_step.py``) over ``kda_decode_step``'s summed device
time.  A kernel that touched idle slots' state would take longer for the
same count and read low.  A step launches the kernel once a KDA layer; the
slice's edges cut steps (``kda_prefill_roofline``'s ``share``).  A program
without the kernel or the attribute gives nothing to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    return load_module("metrics", "kda_prefill_roofline").share(
        result, ctx, "kda_decode_roofline", "kda_decode_step", "engine.step",
        "state_bytes", lambda kc, a, d: kc.cost(a["state_bytes"]))

"""The flash attention kernels' share of their roofline: the least time the
chip could take for what the calls need (the larger of operations over peak
FLOP/s and bytes over peak bytes/s) over the kernels' summed device time in
the traced slice.  Operations and bytes: ``kernel_costs/flash_attention.py``.
"""
from benchmarks.harness import trace_reduce
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    trace, f = result.get("trace"), result["facts"]
    if trace is None or ctx.peaks is None:
        return None
    kc = load_module("kernel_costs", "flash_attention")
    t_f, n_f = trace_reduce.kernel_seconds(trace, kc.FORWARD)
    t_b, n_b = trace_reduce.kernel_seconds(trace, kc.BACKWARD)
    if n_f + n_b == 0:
        return None
    d = f["dims"]
    flops, nbytes = kc.cost(n_f, n_b, batch=f["batch"], heads=d["heads"],
                            seq=f["seq"], head_dim=d["head_dim"])
    by_ops = flops / ctx.peaks["bf16_flops_per_s"]
    by_bytes = nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.say(f"flash_roofline: {n_f} forward + {n_b} backward kernel events, "
            f"{t_f + t_b:.4f}s on the device; least time by operations "
            f"{by_ops:.4f}s, by bytes {by_bytes:.4f}s -> bound by "
            f"{'operations' if by_ops >= by_bytes else 'bytes'}")
    return 100.0 * max(by_ops, by_bytes) / (t_f + t_b)

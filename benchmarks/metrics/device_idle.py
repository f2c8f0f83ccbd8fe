"""Share of the traced slice in which no operation ran on the device
(profiler trace; averaged over the chips used)."""
from benchmarks.harness import trace_reduce


def read(result, ctx):
    trace = result.get("trace")
    if trace is None or not trace.device_ops:
        return None
    return 100.0 * trace_reduce.idle_share(trace)

"""Device-busy milliseconds per training step: for every whole execution of
the program that took most device time (the step) in the traced slice, the union of its operations'
intervals; the mean over those executions."""
from benchmarks.harness import trace_reduce


def read(result, ctx):
    trace = result.get("trace")
    if trace is None:
        return None
    runs = trace_reduce.main_program_runs(trace)
    if not runs:
        return None
    busy = [trace_reduce.busy_within(trace, s, e) for s, e in runs]
    return 1e3 * sum(busy) / len(busy)

"""Device-busy milliseconds of one whole execution of a prefill program
(``jit_prefill_step`` on the trace's ``XLA Modules`` line, whatever its
bucket); the median over its executions in the traced slice."""
from benchmarks.harness import program_spans as ps

PROGRAM = "jit_prefill_step"


def read(result, ctx):
    return ps.program_device_ms(result, PROGRAM, ctx.say)

"""Device-busy milliseconds of one whole execution of the decode program,
found on the trace's ``XLA Modules`` line by the name the program gives it
(``jit_decode_step``); the median over its executions in the traced slice.
The serving twin of ``train_step_device_ms``, by name instead of "the program
that took most time"."""
from benchmarks.harness import program_spans as ps

PROGRAM = "jit_decode_step"


def read(result, ctx):
    return ps.program_device_ms(result, PROGRAM, ctx.say)

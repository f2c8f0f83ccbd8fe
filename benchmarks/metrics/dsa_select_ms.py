"""Device milliseconds a whole execution of ``jit_decode_step`` spends under
``dsa.select``: the cut's counting passes over the index scores and the list
of selected rows, XLA operations that keep XLA's names in a trace
(``harness/device_scopes.py`` joins them to the program's own scope map)."""
from benchmarks.harness import device_scopes

PROGRAM, SCOPE = "jit_decode_step", "dsa.select"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, PROGRAM, SCOPE, ctx.say)

"""Device milliseconds a whole execution of ``jit_decode_step`` spends under
``model.head``: the final norm to the logits (``harness/device_scopes.py``
joins the slice's events to the program's own scope map)."""
from benchmarks.harness import device_scopes

PROGRAM, SCOPE = "jit_decode_step", "model.head"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, PROGRAM, SCOPE, ctx.say)

"""Device milliseconds a whole execution of the train step's program spends
under the streamed fused cross-entropy's scope (``loss.streamed_ce``),
forward and backward: the events of the traced slice put down to the scope
their HLO instruction was traced under (``harness/device_scopes.py``; the map
is the program's own).  ``None`` where the program states no map."""
from benchmarks.harness import device_scopes

SCOPE = "loss.streamed_ce"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, None, SCOPE, ctx.say)

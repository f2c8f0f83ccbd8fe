"""Device milliseconds a whole execution of the train step's program spends
under the optimizer's scope (``optimizer.adamw``): as ``ce_device_ms``, read
by ``harness/device_scopes.py`` from the program's own scope map."""
from benchmarks.harness import device_scopes

SCOPE = "optimizer.adamw"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, None, SCOPE, ctx.say)

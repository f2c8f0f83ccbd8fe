"""Device milliseconds a whole execution of ``jit_decode_step`` spends under
the convolution operators' scopes: every reading whose path holds the
``conv`` layer (its two projections, the gates and the filter under
``conv.mix``, the state's shift under ``state.write``), all such layers
together (``harness/device_scopes.py`` joins the slice's events to the
program's own scope map).  A program without such a layer gives nothing to
read."""
from benchmarks.harness import device_scopes

PROGRAM, SCOPE = "jit_decode_step", "conv"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, PROGRAM, SCOPE, ctx.say)

"""Device milliseconds a whole execution of ``jit_prefill_step`` spends under
the KDA operators' scopes: every reading whose path holds the ``kda`` layer
(its projections, the convolution, gates and norms under ``kda.mix``, the
scan under ``kda.scan``, the state's writes under ``state.write``), all such
layers together (``harness/device_scopes.py`` joins the slice's events to the
program's own scope map).  A program without such a layer gives nothing to
read."""
from benchmarks.harness import device_scopes

PROGRAM, SCOPE = "jit_prefill_step", "kda"


def read(result, ctx):
    return device_scopes.program_scope_ms(result, PROGRAM, SCOPE, ctx.say)

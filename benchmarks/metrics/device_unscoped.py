"""Share of the device-busy time of the traced slice's whole program
executions that no scope map puts under a scope: no map for the module, the
instruction not in it, ambiguous between same-named programs, or read
unscoped (``harness/device_scopes.py``, which prints the by-scope table a
program).  ``device_unscoped.train`` and ``device_unscoped.serve`` are this
one quantity, split by the end-to-end metric it moves."""
from benchmarks.harness import device_scopes


def read(result, ctx):
    return device_scopes.unscoped_share(result, ctx.say)

"""Share of the train step's device-busy time spent in the streamed fused
cross-entropy, forward and backward: device events that carry the program's
``loss.streamed_ce`` scope, over the device-busy time of the step program's
whole executions in the traced slice.

**Not declared in ``BENCHMARK.json``**: on jax 0.9.0 / libtpu 0.0.34 an ``XLA
Ops`` event of a fusion or a ``while`` is named by the instruction's text
without its metadata and carries no string stat, so the scope reaches no field
this reader sees and it returns ``None`` (PERF.md §7 says what would change
that).  Kept, with its test, for the PR that makes the scope readable."""
from benchmarks.harness import program_spans as ps

SCOPE = "loss.streamed_ce"


def read(result, ctx):
    return ps.scope_share(result, SCOPE, ctx.say)

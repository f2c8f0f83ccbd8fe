"""Seconds of set-up spent tracing Python into programs: the sum of the
program's ``jit.trace`` spans (one per program-cache miss: ``prog.build``'s
discovery to a fixed point) that ended before the window's start.  The reader
prints ``jit.compile`` (each miss's first execution: jax's own trace, the
lowering, and the XLA compile or persistent-cache load) beside it, by
function."""
from benchmarks.harness import program_spans as ps


def read(result, ctx):
    start = ps.window_start(result)
    rows = ps.rows()
    if start is None or not rows:
        return None
    traced = ps.named(rows, "jit.trace", t1=start)
    if not traced:
        return None
    compiled = ps.named(rows, "jit.compile", t1=start)
    by_fn = {}
    for r in traced + compiled:
        fn = str(r[ps.ATTRS].get("fn", "?")).rpartition(".")[2]
        pair = by_fn.setdefault(fn, [0.0, 0.0, 0])
        pair[0 if r[ps.NAME] == "jit.trace" else 1] += ps.seconds(r)
        pair[2] += r[ps.NAME] == "jit.trace"
    ctx.say("setup_trace_s: jit.trace "
            f"{sum(ps.seconds(r) for r in traced):.2f}s, jit.compile "
            f"{sum(ps.seconds(r) for r in compiled):.2f}s before the window; "
            "by function (misses, trace s, compile s) "
            f"{ {k: (v[2], round(v[0], 2), round(v[1], 2)) for k, v in by_fn.items()} }")
    return sum(ps.seconds(r) for r in traced)

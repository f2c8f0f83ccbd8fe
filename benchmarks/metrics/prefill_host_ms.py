"""Median host milliseconds of one admitted request's prefill: its
``engine.prefill`` span (dispatch of the bucket's program) plus its
``engine.first_token`` span (the blocking pull of the sampled token), children
of the program's ``engine.admit`` span, over the window as far as the
profiler's start.  With ``queue_wait_p50_ms`` it splits ``ttft_p50_ms``."""
import statistics

from benchmarks.harness import program_spans as ps

PARTS = ("engine.prefill", "engine.first_token")


def read(result, ctx):
    quiet = ps.quiet_window(result)
    rows = ps.rows()
    if quiet is None or not rows:
        return None
    kids = ps.children(rows)
    took = []
    for r in ps.named(rows, "engine.admit", *quiet):
        if r[ps.ATTRS].get("outcome") != "admitted":
            continue
        parts = [c for c in kids.get(r[ps.SID], ()) if c[ps.NAME] in PARTS]
        if parts:
            took.append(sum(ps.seconds(c) for c in parts))
    return 1e3 * statistics.median(took) if took else None

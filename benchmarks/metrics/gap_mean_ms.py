"""Mean gap between one request's consecutive tokens over all gaps of the
window (the ``itl_mean_ms`` of the first check, under another name).  A
per-layer metric: one host stall of 2 s under 20 running slots adds 40 s to
a sum of some 660 s, and the first check read it 6 % apart in runs of one
code (PERF.md, PR 23); a PR is held to ``tpot_p50_ms``, which a stall moves
a third as far.  Read over the window as far as the profiler's start."""


def read(result, ctx):
    return result["host_quiet"].get("itl_mean_ms")

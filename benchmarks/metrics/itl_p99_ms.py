"""99th percentile of the gaps between one request's consecutive tokens,
stamped in ``stream_cb`` on the benchmark's clock.  A per-layer metric: it
sits among the gaps that a 1024-bucket prefill stalls, some 2 % of all, and
spreads by 8 % from seed to seed (PERF.md, PR 23); a PR is held to
``tpot_p50_ms``.  Read over the window as far as the profiler's start."""


def read(result, ctx):
    return result["host_quiet"].get("itl_p99_ms")

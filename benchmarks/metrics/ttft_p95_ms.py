"""95th percentile, over the requests due in the window, of the first
token's time minus the time the request was due (open loop; a request that
never got a token counts as the window's length).  The tail a chat user
feels, and a per-layer metric all the same: over the 140 requests that the
longest window holds it spreads by an eighth from seed to seed (PERF.md,
PR 23), more than any bound may be, so a PR is held to ``ttft_p50_ms``.
Read over the window as far as the profiler's start, which stalls the loop."""


def read(result, ctx):
    return result["host_quiet"].get("ttft_p95_ms")

"""Median host milliseconds of one admitted request between the start of its
admission and the pull of its first token, outside the dispatch of its
prefill: (``engine.first_token``'s start − the ``engine.admit`` span's start)
less its ``engine.prefill`` children, over the window as far as the profiler's
start.  The prefix lookup, the slot's block assignment and staging, and
whatever else the scheduler does before it asks the device for the token —
serial with the device, and in none of ``prefill_host_ms``'s spans.  With
``queue_wait_p50_ms`` and ``prefill_host_ms`` it splits ``ttft_p50_ms``."""
import statistics

from benchmarks.harness import program_spans as ps


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
        mine = kids.get(r[ps.SID], ())
        pulls = [c for c in mine if c[ps.NAME] == "engine.first_token"]
        if not pulls:
            continue
        took.append(pulls[0][ps.START] - r[ps.START] - sum(
            ps.seconds(c) for c in mine if c[ps.NAME] == "engine.prefill"))
    return 1e3 * statistics.median(took) if took else None

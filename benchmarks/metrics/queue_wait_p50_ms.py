"""Median milliseconds a request waited between ``add_request`` and the start
of its admission: ``queue_wait_ms`` of the program's ``engine.admit`` spans
with ``outcome == "admitted"`` in the window (as far as the profiler's start).
The part of the time to first token spent behind the running step and the
prompts ahead, before the request's own prefill."""
import statistics

from benchmarks.harness import program_spans as ps


def read(result, ctx):
    quiet = ps.quiet_window(result)
    if quiet is None:
        return None
    waits = [r[ps.ATTRS]["queue_wait_ms"]
             for r in ps.named(ps.rows(), "engine.admit", *quiet)
             if r[ps.ATTRS].get("outcome") == "admitted"
             and "queue_wait_ms" in r[ps.ATTRS]]
    return statistics.median(waits) if waits else None

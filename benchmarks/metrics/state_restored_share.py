"""Admissions whose prefill started from a snapshot of the state group, as a
share of all admitted, over the window as far as the profiler's start: the
program's ``engine.prefill`` spans carry ``state_row`` (the snapshot row the
program read its first state from; 0: the zeros, a cold start).  100 % where
every prompt's prefix is resident with its snapshot; it falls when snapshots
are evicted before they are hit again, and the tail then re-prefills what the
K/V group still holds.  A program whose spans lack the attribute gives nothing
to read."""
from benchmarks.harness import program_spans as ps


def prefills(result):
    """The attributes of the quiet window's admissions into a cache with a
    state group."""
    quiet = ps.quiet_window(result)
    rows = ps.rows()
    if quiet is None or not rows:
        return []
    return [r[ps.ATTRS] for r in ps.named(rows, "engine.prefill", *quiet)
            if "state_row" in r[ps.ATTRS]]


def read(result, ctx):
    got = prefills(result)
    if not got:
        return None
    restored = sum(a["state_row"] > 0 for a in got)
    ctx.say(f"state_restored_share: {restored} of {len(got)} admissions "
            f"started from a snapshot; they wrote "
            f"{sum(a.get('state_snapshots_written', 0) for a in got)} "
            f"snapshots and their hits gave up "
            f"{sum(a.get('state_hit_given_up', 0) for a in got)} tokens")
    return 100.0 * restored / len(got)

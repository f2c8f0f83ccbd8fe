"""The snapshot pool at its fullest: over the decode steps of the window as
far as the profiler's start, the rows that hold a snapshot (a live slot's or
the prefix cache's: ``state_snapshots_used`` on the program's ``engine.step``
spans, from the allocator's running counts) at their peak over the pool's
rows (``state_snapshots``).  At 100 % every new snapshot evicts the oldest
idle one, and what it evicts shows in ``state_restored_share``.  The line
it says also gives the K/V group's blocks that a live slot held at their peak
(``swa_blocks_used`` of ``swa_blocks``: what ``cache_group_peak`` reads in
the cells it lists).  A program whose spans lack the attributes gives nothing
to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    got = [a for a in load_module("metrics", "swa_attended_share"
                                  ).steps(result)
           if a.get("state_snapshots", 0) > 0]
    if not got:
        return None
    peak = max(a["state_snapshots_used"] for a in got)
    blocks = [max(a["swa_blocks_used"][g] for a in got)
              for g in range(len(got[0]["swa_blocks"]))]
    ctx.say(f"state_snapshot_peak: {peak} of {got[0]['state_snapshots']} "
            f"snapshot rows held at the peak over {len(got)} decode steps "
            f"(first {got[0]['state_snapshots_used']}, last "
            f"{got[-1]['state_snapshots_used']}); blocks a live slot held "
            f"at their peak, by group {blocks} of "
            f"{list(got[0]['swa_blocks'])}")
    return 100.0 * peak / got[0]["state_snapshots"]

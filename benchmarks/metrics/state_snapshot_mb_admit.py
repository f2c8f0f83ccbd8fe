"""Megabytes of state snapshots an admission's prefill writes, the mean over
the window as far as the profiler's start: the program's ``engine.prefill``
spans carry ``state_bytes_snapshotted`` (snapshot rows the program writes x
what a row weighs: every state layer's sides of one slot).  It is what the
group's snapshot stride and the replay snapshot cost in pool rows and in
writes; a model whose state is kilobytes reads near 0, one whose state is a
recurrence tens of MB.  A program whose spans lack the attribute gives
nothing to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    got = [a for a in load_module("metrics", "state_restored_share"
                                  ).prefills(result)
           if "state_bytes_snapshotted" in a]
    if not got:
        return None
    written = sum(a["state_bytes_snapshotted"] for a in got)
    restored = sum(a.get("state_bytes_restored", 0) for a in got)
    ctx.say(f"state_snapshot_mb_admit: {len(got)} admissions wrote "
            f"{written / 1e6:.1f} MB of snapshots "
            f"({sum(a['state_snapshots_written'] for a in got)} rows) and "
            f"restored {restored / 1e6:.1f} MB")
    return written / 1e6 / len(got)

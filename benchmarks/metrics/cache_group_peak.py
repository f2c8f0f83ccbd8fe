"""The fuller of a by-layer cache's groups at its fullest: over the decode
steps of the window as far as the profiler's start, each group's blocks that
a live slot holds (the program's ``engine.step`` spans carry
``swa_blocks_used`` beside each group's ``swa_blocks``, read from the
allocators' running counts) at their peak over the group's blocks; the larger
of the groups' shares.  Blocks that only the prefix cache holds are not in
it: they go when an admission wants them.  Which group it is says which one
gates admission under this traffic.  A program whose spans lack the
attributes gives nothing to read."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    got = [a for a in load_module("metrics", "swa_attended_share"
                                  ).steps(result) if "swa_blocks_used" in a]
    if not got:
        return None
    peaks = [max(a["swa_blocks_used"][g] for a in got)
             for g in range(len(got[0]["swa_blocks"]))]
    shares = [100.0 * p / n for p, n in zip(peaks, got[0]["swa_blocks"])]
    ctx.say(f"cache_group_peak: blocks a live slot held at their peak, by "
            f"group {peaks} of {list(got[0]['swa_blocks'])} "
            f"({', '.join(f'{s:.1f} %' for s in shares)})")
    return max(shares)

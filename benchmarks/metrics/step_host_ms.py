"""Host milliseconds of a decode-only scheduler step outside its blocking
pull: the median, over the program's ``engine.step`` spans of the window (as
far as the profiler's start) that admitted no prompt and left slots running,
of the step's duration minus its ``engine.pull`` child.  What the host adds to
every token's gap on top of the device's step (ROADMAP S4 overlaps it)."""
import statistics

from benchmarks.harness import program_spans as ps


def read(result, ctx):
    quiet = ps.quiet_window(result)
    rows = ps.rows()
    if quiet is None or not rows:
        return None
    kids = ps.children(rows)
    host = []
    for r in ps.named(rows, "engine.step", *quiet):
        a = r[ps.ATTRS]
        if a.get("admitted") != 0 or not a.get("running", 0) > 0:
            continue
        pull = sum(ps.seconds(c) for c in kids.get(r[ps.SID], ())
                   if c[ps.NAME] == "engine.pull")
        host.append(ps.seconds(r) - pull)
    if not host:
        return None
    ctx.say(f"step_host_ms: {len(host)} decode-only steps; host ms outside "
            f"the pull median {1e3 * statistics.median(host):.3f} max "
            f"{1e3 * max(host):.3f}")
    return 1e3 * statistics.median(host)

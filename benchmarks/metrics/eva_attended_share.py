"""Rows the running slots attended to (exact positions of their own window
and summary rows of the windows passed) as a share of the tokens they had,
over the decode steps of the window as far as the profiler's start: the
program's ``engine.step`` spans carry what their decode program counted
(``eva_exact_rows``, ``eva_summary_rows``, ``eva_context``: summed over the
running slots, one layer's count).  About 12 % at 28k positions (13 windows
of 128 summary rows and up to 2,048 exact positions); 100 % would mean every
token was read exact.  A program whose spans lack the attributes gives
nothing to read."""
from benchmarks.harness import program_spans as ps


def steps(result, t0=None, t1=None):
    """The attributes of the decode steps in ``[t0, t1]`` (default: the quiet
    window) whose program counted rows."""
    rows = ps.rows()
    if t0 is None:
        quiet = ps.quiet_window(result)
        if quiet is None:
            return []
        t0, t1 = quiet
    if not rows:
        return []
    return [r[ps.ATTRS] for r in ps.named(rows, "engine.step", t0, t1)
            if r[ps.ATTRS].get("eva_context", 0) > 0]


def read(result, ctx):
    got = steps(result)
    if not got:
        return None
    exact = sum(a["eva_exact_rows"] for a in got)
    summary = sum(a["eva_summary_rows"] for a in got)
    context = sum(a["eva_context"] for a in got)
    ctx.say(f"eva_attended_share: {len(got)} decode steps, {exact} exact and "
            f"{summary} summary rows for {context} tokens (a layer's count, "
            f"summed over running slots); {context / len(got):.0f} tokens a "
            f"step; windows published in those steps "
            f"{sum(a.get('eva_windows_published', 0) for a in got)}")
    return 100.0 * (exact + summary) / context

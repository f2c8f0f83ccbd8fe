"""Keys the running slots' layers read as a share of what every layer would
read were all of them full, over the decode steps of the window as far as the
profiler's start: ``(full layers x full rows + window layers x window rows) /
(layers x context)``.  The program's ``engine.step`` spans carry the counts
(``swa_full_rows``, ``swa_window_rows``, ``swa_context``: a layer's count of
each kind, summed over the running slots, by the kernels' own rule from the
lengths the host knows).  With 7 full layers of 28 and a window of 1,024 a
slot at 30.7k tokens reads 27.5 %; a short slot inside its window 100 %.  A
program whose spans lack the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps
from benchmarks.harness.manifest import load_module


def steps(result, t0=None, t1=None):
    """The attributes of the decode steps in ``[t0, t1]`` (default: the quiet
    window) of a program that states its cache by layer."""
    rows = ps.rows()
    if t0 is None:
        quiet = ps.quiet_window(result)
        if quiet is None:
            return []
        t0, t1 = quiet
    if not rows:
        return []
    return [r[ps.ATTRS] for r in ps.named(rows, "engine.step", t0, t1)
            if r[ps.ATTRS].get("swa_context", 0) > 0]


def read(result, ctx):
    d = result["facts"].get("dims") or {}
    got = steps(result)
    if not got or "full_layers" not in d:
        return None
    kc = load_module("kernel_costs", "swa_paged_decode")
    full = sum(a["swa_full_rows"] for a in got)
    window = sum(a["swa_window_rows"] for a in got)
    context = sum(a["swa_context"] for a in got)
    ctx.say(f"swa_attended_share: {len(got)} decode steps, {full} keys a "
            f"full layer and {window} a window layer for {context} tokens "
            f"(summed over running slots); {context / len(got):.0f} tokens "
            f"a step")
    return 100.0 * kc.mean_rows(full, window, layers=d["layers"],
                                full_layers=d["full_layers"]) / context

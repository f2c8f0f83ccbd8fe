"""Tokens the running slots attended to as a share of the tokens they had
cached, over the decode steps of the window as far as the profiler's start:
the program's ``engine.step`` spans carry what their decode program counted
(``dsa_selected``, ``dsa_context``: summed over the running slots, one
layer's mean).  About ``topk`` over the context where the indexed path runs
(2,048 of ~31,000: 6.6 %); 100 % would mean the dense path ran.  A program
whose spans lack the attributes gives nothing to read."""
from benchmarks.harness import program_spans as ps


def steps(result, t0=None, t1=None):
    """The attributes of the decode steps in ``[t0, t1]`` (default: the quiet
    window) whose program counted a selection."""
    rows = ps.rows()
    if t0 is None:
        quiet = ps.quiet_window(result)
        if quiet is None:
            return []
        t0, t1 = quiet
    if not rows:
        return []
    return [r[ps.ATTRS] for r in ps.named(rows, "engine.step", t0, t1)
            if r[ps.ATTRS].get("dsa_context", 0) > 0]


def read(result, ctx):
    got = steps(result)
    if not got:
        return None
    selected = sum(a["dsa_selected"] for a in got)
    context = sum(a["dsa_context"] for a in got)
    ctx.say(f"dsa_selected_share: {len(got)} decode steps, {selected} of "
            f"{context} cached tokens selected (a layer's mean, summed over "
            f"running slots); {context / len(got):.0f} cached tokens a step")
    return 100.0 * selected / context

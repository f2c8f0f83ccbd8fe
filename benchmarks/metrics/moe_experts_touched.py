"""Mean number of held experts that received a token, per expert layer per
decode step, over the window as far as the profiler's start: the program's
``engine.step`` spans carry what their decode program counted
(``moe_experts_touched``, summed over the expert layers).  It is what sets
the decode step's expert weight traffic: each touched expert's three matrices
are read once.  A program whose spans lack the attribute gives nothing to
read."""
from benchmarks.harness import program_spans as ps


def steps(result):
    """The attributes of the window's decode steps that routed tokens."""
    quiet = ps.quiet_window(result)
    rows = ps.rows()
    if quiet is None or not rows:
        return []
    return [r[ps.ATTRS] for r in ps.named(rows, "engine.step", *quiet)
            if r[ps.ATTRS].get("moe_tokens", 0) > 0]


def read(result, ctx):
    d = result["facts"].get("dims") or {}
    got = steps(result)
    if not got or "dense_layers" not in d:
        return None
    layers = d["layers"] - d["dense_layers"]
    touched = sum(a["moe_experts_touched"] for a in got)
    ctx.say(f"moe_experts_touched: {len(got)} decode steps, {layers} expert "
            f"layers, {touched} (layer, expert) pairs got a token; "
            f"{sum(a['moe_tokens'] for a in got) / len(got):.2f} tokens a "
            f"step")
    return touched / float(len(got) * layers)

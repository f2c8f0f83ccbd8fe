"""Share of a window's latent-attention prefill pairs (query, key) computed
in the up-projected form: ``latent_pairs_upprojected`` over both
``latent_pairs_*`` of the program's ``engine.prefill`` spans, over the window
as far as the profiler's start.  The rest took the absorbed form (a tail's
queries over a cached prefix).  A program whose spans lack the counts gives
nothing to read."""
from benchmarks.harness import program_spans as ps


def read(result, ctx):
    quiet = ps.quiet_window(result)
    if quiet is None:
        return None
    got = [r[ps.ATTRS] for r in ps.named(ps.rows(), "engine.prefill", *quiet)
           if "latent_pairs_upprojected" in r[ps.ATTRS]]
    up = sum(a["latent_pairs_upprojected"] for a in got)
    absorbed = sum(a.get("latent_pairs_absorbed", 0) for a in got)
    if up + absorbed <= 0:
        return None
    ctx.say(f"latent_upprojected_share: {len(got)} prefills, {up} pairs "
            f"up-projected, {absorbed} absorbed")
    return 100.0 * up / (up + absorbed)

"""Share of the traced slice in which the first device plane is idle AND the
scheduler is in no blocking pull (``engine.pull`` of a decode step,
``engine.first_token`` of a prefill): idle time that the host's own work
causes, which dispatching step N+1 before consuming step N (ROADMAP S4) can
win.  The program's spans come from its ring on ``perf_counter`` and are laid
over the device's events through ``program_spans.clock_offset``; the reader
also prints the idle seconds by innermost program span."""
from benchmarks.harness import program_spans as ps

PULLS = ("engine.pull", "engine.first_token")


def read(result, ctx):
    trace = result.get("trace")
    rows = ps.rows()
    if trace is None or not trace.device_ops or not rows:
        return None
    offset = ps.clock_offset(result, ctx.say)
    if offset is None:
        return None
    t0, t1 = trace.window()
    shifted = [(r[ps.NAME], r[ps.START] + offset, r[ps.END] + offset)
               + tuple(r[3:]) for r in rows
               if r[ps.END] + offset > t0 and r[ps.START] + offset < t1]
    if not any(r[ps.NAME] == "engine.step" for r in shifted):
        return None
    idle = ps.idle_intervals(trace)
    idle_s = sum(b - a for a, b in idle)
    pulling = [(r[ps.START], r[ps.END]) for r in shifted
               if r[ps.NAME] in PULLS]
    in_pull = ps.overlap(idle, pulling)
    by = ps.idle_by_span(trace, shifted)
    ctx.say(f"idle_outside_pull: {len(shifted)} program rows in the slice; "
            f"{idle_s:.4f}s idle of {t1 - t0:.4f}s, "
            f"{in_pull:.4f}s inside a pull; idle seconds by innermost "
            f"program span "
            f"{ {k: round(v, 4) for k, v in sorted(by.items(), key=lambda kv: -kv[1])} }")
    return 100.0 * (idle_s - in_pull) / (t1 - t0) if t1 > t0 else None

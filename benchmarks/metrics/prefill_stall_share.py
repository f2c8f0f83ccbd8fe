"""Share of the window's wall time spent inside ``engine.step()`` calls that
admitted at least one prompt: the time every running slot's next token waited
behind a prefill (benchmark span; the window as far as the profiler's start,
which stalls the loop)."""


def read(result, ctx):
    f = result["facts"]
    if f.get("kind") != "serve":
        return None
    t0, t1 = f["quiet_window"]
    stalled = sum(min(e, t1) - s for name, s, e, a in result["spans"]
                  if name == "engine.step" and a.get("admitted", 0) > 0
                  and t0 <= s < t1)
    return 100.0 * stalled / (t1 - t0)

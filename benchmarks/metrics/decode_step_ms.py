"""Median host milliseconds of the window's ``engine.step()`` calls that ran
slots and admitted no prompt (benchmark span; the window as far as the
profiler's start, which stalls the loop)."""
import statistics


def read(result, ctx):
    t0, t1 = result["facts"]["quiet_window"]
    xs = [e - s for name, s, e, a in result["spans"]
          if name == "engine.step" and a.get("admitted") == 0
          and a.get("busy", 0) > 0 and t0 <= s and e <= t1]
    return 1e3 * statistics.median(xs) if xs else None

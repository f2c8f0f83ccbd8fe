"""Mean share of the engine's slots that were running after each of the
window's ``engine.step()`` calls, sampled by the benchmark (not the engine's
own figure, which averages from engine start)."""


def read(result, ctx):
    f = result["facts"]
    if f.get("kind") != "serve":
        return None
    t0, t1 = f["quiet_window"]
    busy = [a["busy"] for name, s, e, a in result["spans"]
            if name == "engine.step" and "busy" in a and t0 <= s and e <= t1]
    return 100.0 * sum(busy) / (len(busy) * f["num_slots"]) if busy else None

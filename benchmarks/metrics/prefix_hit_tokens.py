"""Share of looked-up prompt tokens that the prefix cache served, over the
window: ``stats()["paging"]["prefix"]`` ``hit_tokens`` over
``lookup_tokens``, as the difference between the window's end and its start
(the counters are cumulative and set-up moves them)."""


def read(result, ctx):
    a = result["counters"].get("prefix_start") or {}
    b = result["counters"].get("prefix_end") or {}
    looked = b.get("lookup_tokens", 0) - a.get("lookup_tokens", 0)
    if looked <= 0:
        return None
    return 100.0 * (b["hit_tokens"] - a.get("hit_tokens", 0)) / looked

"""Assignments to the held experts as a share of all the decode tokens'
assignments (tokens x experts per token x expert layers), over the window as
far as the profiler's start; from the program's ``engine.step`` spans
(``moe_assignments_held``, ``moe_tokens``).  12.5 % when 32 of 256 experts are
held and routing is even; what lies above or below is the seeded router's
skew towards or away from this chip's experts."""
from benchmarks.harness.manifest import load_module


def read(result, ctx):
    d = result["facts"].get("dims") or {}
    got = load_module("metrics", "moe_experts_touched").steps(result)
    if not got or "top_k" not in d:
        return None
    layers = d["layers"] - d["dense_layers"]
    held = sum(a["moe_assignments_held"] for a in got)
    tokens = sum(a["moe_tokens"] for a in got)
    return 100.0 * held / (tokens * d["top_k"] * layers)

"""Model FLOP/s utilisation of the window: tokens per second per chip (from
the median time between steps, so that the profiler's start and stop inside a
traced window do not count) times
the operations a token needs, over the chip's bf16 peak.  Per token, forward
and backward: 6 x the weights that are multiplied (per layer 4 h^2 for Q, K,
V, O and 2 h ffn for the MLP; the vocabulary head once; the embedding gather
not counted) plus 6 x layers x seq x h for causal attention (two matmuls over
half of seq x seq, forward and twice backward).  Not 6ND over all parameters;
recomputed operations do not count."""


def flops_per_token(d: dict, seq: int) -> float:
    h = d["hidden"]
    multiplied = d["layers"] * (4 * h * h + 2 * h * d["ffn"]) + d["vocab"] * h
    return 6.0 * multiplied + 6.0 * d["layers"] * seq * h


def read(result, ctx):
    f = result["facts"]
    if ctx.peaks is None or f.get("kind") != "train":
        return None
    return 100.0 * f["steady_tokens_per_s_chip"] * flops_per_token(
        f["dims"], f["seq"]) / ctx.peaks["bf16_flops_per_s"]

"""The one general generator of serving traffic.  A mix file's ``params``
choose everything; a new mix is a new data file, never new code.

``params``:

- ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` — an open loop: the
  schedule goes on whether or not earlier requests finished — or
  ``{"kind": "closed", "clients": c, "requests_per_client": k}`` — each
  client sends its next request when its last completed.
- ``prompt_tokens`` / ``output_tokens``: ``{"median", "sigma", "min", "max"}``
  log-normal, clipped; ``max_total_tokens`` caps prompt + output.
- ``shared_prefixes``: ``{"count", "tokens", "share"}`` — ``share`` of the
  requests begin with one of ``count`` fixed prefixes of ``tokens`` tokens.
- ``sampled``: ``{"share", "temperature", "top_k", "top_p"}`` — that share of
  the requests sample; the rest decode greedily.

Every seed offers the same work in another order.  The *set* of requests
(prompt length, output length, shared or not, sampled or not) and the set of
gaps between arrivals are the parameters' alone: the quantiles of the
log-normal and of the exponential distribution, as many as the window holds
requests, paired by a permutation that never changes.  ``seed`` draws the
order in which the requests come, the order of the gaps, the tokens, the
shared prefixes and the sampling seeds.  So two seeds send two different
schedules of the same total work, and one seed sends the same schedule every
time.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_PAIRING = 0x706F70      # pairs lengths and flags; the same for every seed


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    nd = statistics.NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    v = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(p)) for p in q]
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def _flags(n: int, share: float, rng) -> np.ndarray:
    out = np.zeros(n, bool)
    out[rng.permutation(n)[:int(round(share * n))]] = True
    return out


def population(params: dict, n: int) -> dict:
    """The set of ``n`` requests' sizes and flags (no tokens, no order yet):
    the parameters' quantiles under a pairing that no seed changes."""
    rng = np.random.default_rng(_PAIRING)
    prompt = _lognormal_quantiles(n, params["prompt_tokens"])
    output = _lognormal_quantiles(n, params["output_tokens"])[
        rng.permutation(n)]
    sp = params.get("shared_prefixes")
    shared = _flags(n, sp["share"], rng) if sp else np.zeros(n, bool)
    if sp:   # a shared prompt holds the prefix and at least a block more
        prompt = np.where(shared, np.maximum(prompt, sp["tokens"] + 16),
                          prompt)
    cap = params.get("max_total_tokens")
    if cap:
        output = np.minimum(output, cap - prompt)
    sa = params.get("sampled")
    sampled = _flags(n, sa["share"], rng) if sa else np.zeros(n, bool)
    return {"prompt": prompt, "output": output, "shared": shared,
            "sampled": sampled}


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` due times in ``[0, seconds)``: the exponential distribution's
    quantiles as gaps, in ``rng``'s order, scaled to fill the window."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    t = np.cumsum(gaps[rng.permutation(n)])
    return t / t[-1] * seconds * n / (n + 0.5)


def generate(params: dict, seed: int, *, seconds: float, vocab: int) -> dict:
    """``{"kind", "clients", "requests": [...]}``; a request is ``{"due_s" |
    "client", "prompt" (int32 array), "max_new_tokens", "sampling" (dict or
    None), "shared" (prefix index or -1)}``, in arrival order."""
    arr = params["arrivals"]
    rng = np.random.default_rng([int(seed), 0x73657276])
    if arr["kind"] == "poisson":
        n = max(1, int(round(arr["rate_per_s"] * seconds)))
    elif arr["kind"] == "closed":
        n = int(arr["clients"]) * int(arr["requests_per_client"])
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    pop = population(params, n)
    order = rng.permutation(n)
    due = arrival_times(n, seconds, rng) if arr["kind"] == "poisson" else None
    sp, sa = params.get("shared_prefixes"), params.get("sampled")
    prefixes = (rng.integers(0, vocab, (sp["count"], sp["tokens"]),
                             dtype=np.int32) if sp else None)
    requests = []
    for slot, j in enumerate(order):
        prompt = rng.integers(0, vocab, (int(pop["prompt"][j]),),
                              dtype=np.int32)
        which = -1
        if pop["shared"][j]:
            which = int(rng.integers(0, sp["count"]))
            prompt[:sp["tokens"]] = prefixes[which]
        sampling = None
        if pop["sampled"][j]:
            sampling = {"temperature": sa["temperature"],
                        "top_k": sa["top_k"], "top_p": sa["top_p"],
                        "seed": int(rng.integers(0, 2 ** 31 - 1))}
        req = {"prompt": prompt, "max_new_tokens": int(pop["output"][j]),
               "sampling": sampling, "shared": which}
        if due is not None:
            req["due_s"] = float(due[slot])
        else:
            req["client"] = slot % int(arr["clients"])
        requests.append(req)
    return {"kind": arr["kind"], "clients": int(arr.get("clients", 0)),
            "requests": requests}

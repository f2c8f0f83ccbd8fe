"""The generators: the same seed gives the same traffic, every seed gives
the same work in another order, and the statistics are the mix file's."""
import statistics

import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmarks.generators import sessions, train_batches
from benchmarks.harness.manifest import load_json

CHAT = load_json("mixes", "chat-open.json")
AGENT = load_json("mixes", "agent-closed16.json")
SEEDS = (1, 7, 2 ** 31 + 5)


def _sizes(work):
    return sorted((len(r["prompt"]), r["max_new_tokens"], r["shared"] >= 0,
                   r["sampling"] is not None) for r in work["requests"])


def _gaps(work):
    due = [0.0] + [r["due_s"] for r in work["requests"]]
    return sorted(np.round(np.diff(due), 9))


@pytest.mark.parametrize("mix", [CHAT, AGENT], ids=["chat", "agent"])
def test_same_seed_same_traffic_and_every_seed_the_same_work(mix):
    a = sessions.generate(mix["params"], SEEDS[0], seconds=30, vocab=50304)
    b = sessions.generate(mix["params"], SEEDS[0], seconds=30, vocab=50304)
    assert len(a["requests"]) == len(b["requests"])
    for x, y in zip(a["requests"], b["requests"]):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert {k: v for k, v in x.items() if k != "prompt"} == \
               {k: v for k, v in y.items() if k != "prompt"}
    for seed in SEEDS[1:]:
        c = sessions.generate(mix["params"], seed, seconds=30, vocab=50304)
        assert _sizes(c) == _sizes(a)            # the same set of requests
        order = lambda w: [(len(r["prompt"]), r["max_new_tokens"])  # noqa
                           for r in w["requests"]]
        assert order(c) != order(a)              # ... in another order
        if mix is CHAT:                          # the same gaps, reordered
            assert _gaps(c) == _gaps(a)
            assert [r["due_s"] for r in c["requests"]] != \
                   [r["due_s"] for r in a["requests"]]
        assert not any(np.array_equal(x["prompt"], y["prompt"])  # other tokens
                       for x, y in zip(a["requests"], c["requests"]))


def test_chat_statistics_match_the_mix_file():
    p = CHAT["params"]
    w = sessions.generate(p, 3, seconds=30, vocab=50304)
    reqs = w["requests"]
    assert w["kind"] == "poisson"
    assert len(reqs) == round(p["arrivals"]["rate_per_s"] * 30)
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30
    gaps = np.diff(due)
    assert abs(gaps.mean() * p["arrivals"]["rate_per_s"] - 1) < 0.05
    assert 0.8 < gaps.std() / gaps.mean() < 1.2          # exponential
    drawn = sessions._lognormal_quantiles(len(reqs), p["prompt_tokens"])
    assert abs(statistics.median(drawn) / p["prompt_tokens"]["median"] - 1) \
        < 0.05
    plain = sorted(len(r["prompt"]) for r in reqs if r["shared"] < 0)
    assert set(plain) <= set(drawn.tolist())    # unshared prompts: as drawn
    outs = [r["max_new_tokens"] for r in reqs]
    assert abs(statistics.median(outs) / p["output_tokens"]["median"] - 1) \
        < 0.1
    for r in reqs:
        n = len(r["prompt"])
        assert p["prompt_tokens"]["min"] <= n <= p["prompt_tokens"]["max"]
        assert 1 <= r["max_new_tokens"] <= p["output_tokens"]["max"]
        assert n + r["max_new_tokens"] <= p["max_total_tokens"]
        assert r["prompt"].dtype == np.int32 and r["prompt"].max() < 50304
    shared = [r for r in reqs if r["shared"] >= 0]
    assert len(shared) == round(p["shared_prefixes"]["share"] * len(reqs))
    k = p["shared_prefixes"]["tokens"]
    heads = {r["shared"]: tuple(r["prompt"][:k]) for r in shared}
    assert len(set(heads.values())) == len(heads) <= \
        p["shared_prefixes"]["count"]
    for r in shared:
        assert tuple(r["prompt"][:k]) == heads[r["shared"]]
        assert len(r["prompt"]) > k
    sampled = [r for r in reqs if r["sampling"]]
    assert len(sampled) == round(p["sampled"]["share"] * len(reqs))
    assert sampled[0]["sampling"]["top_k"] == p["sampled"]["top_k"]


def test_agent_mix_is_a_closed_loop_over_one_shared_prompt():
    p = AGENT["params"]
    w = sessions.generate(p, 5, seconds=30, vocab=32768)
    assert w["kind"] == "closed" and w["clients"] == 16
    reqs = w["requests"]
    assert len(reqs) == 16 * p["arrivals"]["requests_per_client"]
    assert {r["client"] for r in reqs} == set(range(16))
    first = tuple(reqs[0]["prompt"][:256])
    for r in reqs:
        assert tuple(r["prompt"][:256]) == first and r["sampling"] is None
        assert 320 <= len(r["prompt"]) <= 2048
        assert 128 <= r["max_new_tokens"] <= 512
    assert abs(statistics.median(r["max_new_tokens"] for r in reqs) / 192
               - 1) < 0.1
    assert max(len(r["prompt"]) + r["max_new_tokens"] for r in reqs) \
        <= AGENT["reference_pad"] <= AGENT["engine"]["max_seq"]


def test_train_batches_are_seeded_and_rows_all_differ():
    x, y = train_batches.generate({"n_batches": 4}, 2 ** 31 + 1, vocab=128,
                                  batch=4, seq=32)
    x2, _ = train_batches.generate({"n_batches": 4}, 2 ** 31 + 1, vocab=128,
                                   batch=4, seq=32)
    x3, _ = train_batches.generate({"n_batches": 4}, 2, vocab=128, batch=4,
                                   seq=32)
    assert x.shape == y.shape == (4, 4, 32) and x.dtype == np.int32
    assert np.array_equal(x, x2) and not np.array_equal(x, x3)
    rows = x.reshape(-1, 32)
    assert len({tuple(r) for r in rows}) == len(rows)
    assert x.max() < 128 and x.min() >= 0

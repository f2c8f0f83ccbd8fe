"""Driver of a serving cell whose documents are resident before the window
(``kind: serve_resident``).

It is ``drivers/serve.py``'s ``run`` with one more set-up step and nothing
else new (``build_engine``, ``prime`` and ``window`` are that file's): after
the engine is built and warmed, every shared prefix ("document") of the
generated work is made resident in the engine's prefix cache, as a replica
that serves questions about a few long documents holds them — a request of
its first ``piece_tokens``, ``2 x piece_tokens``, ... tokens with one new
token each (the mix's ``resident.piece_tokens``), so that every piece is one
prefill bucket's tail behind the part already cached and no cold prompt of
the whole document is ever prefilled.  Then a probe of each document (the
document and a few tokens more) must hit all of its tokens in the prefix
cache; a probe that hits fewer fails the run before the window.  All of it
is set-up: ``setup_s`` carries it, as part of getting a replica ready.

``prime`` is given the mix with its shared prefix cut to two blocks: its
requests only compile the engine's small host-side programs, and a prefix
of a document's length would be a cold prompt of the largest bucket.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.drivers.serve import build_engine, prime, window
from benchmarks.harness.context import CompileCounter, settle_heap
from benchmarks.harness.manifest import load_module

PROBE_TAIL = 19          # tokens a probe adds to its document


def _hit_tokens(eng) -> int:
    return int(((eng.stats()["paging"] or {}).get("prefix") or {}
                ).get("hit_tokens", 0))


def _one_token(eng, prompt) -> None:
    h = eng.add_request(np.asarray(prompt, np.int32), max_new_tokens=1)
    eng.run()
    if not h.finished or len(h.output_ids) != 1:
        raise SystemExit(f"serve_resident: a set-up request of "
                         f"{len(prompt)} tokens did not finish: "
                         f"{getattr(h, 'error', None)}")


def documents(work, tokens: int) -> list:
    """The distinct shared prefixes of the generated work, by index."""
    docs = {}
    for r in work["requests"]:
        if r["shared"] >= 0:
            docs.setdefault(r["shared"], np.asarray(r["prompt"][:tokens]))
    return [docs[k] for k in sorted(docs)]


def make_resident(ctx, eng, work, d) -> dict:
    """Prefills every document of ``work`` piece by piece and probes it;
    returns what it did."""
    sp = ctx.mix["params"]["shared_prefixes"]
    tokens, piece = int(sp["tokens"]), int(ctx.mix["resident"]["piece_tokens"])
    rng = np.random.default_rng([int(ctx.seed), 0x7265736964])
    docs = documents(work, tokens)
    t = time.perf_counter()
    for doc in docs:
        for end in list(range(piece, tokens, piece)) + [tokens]:
            _one_token(eng, doc[:end])
    t_built = time.perf_counter()
    hits = []
    for doc in docs:
        before = _hit_tokens(eng)
        _one_token(eng, np.concatenate([doc, rng.integers(
            0, d["vocab"], (PROBE_TAIL,), dtype=np.int32)]))
        hits.append(_hit_tokens(eng) - before)
    ctx.say(f"resident: {len(docs)} documents of {tokens} tokens in pieces "
            f"of {piece} in {t_built - t:.1f}s; probes hit {hits} tokens in "
            f"{time.perf_counter() - t_built:.1f}s")
    if any(h != tokens for h in hits):
        raise SystemExit(f"serve_resident: a document's probe hit {hits} "
                         f"tokens of {tokens}: it is not resident")
    return {"documents": len(docs), "probe_hits": hits,
            "resident_s": time.perf_counter() - t}


def run(ctx) -> dict:
    family = ctx.config["family"]
    ref = load_module("references", family)
    adapter = load_module("adapters", family)
    d = ref.dims(ctx.config)
    gen = load_module("generators", ctx.mix["generator"])
    work = gen.generate(ctx.mix["params"], ctx.seed, seconds=ctx.seconds,
                        vocab=d["vocab"])
    compiles = CompileCounter()
    eng = build_engine(ctx, adapter, ref, d)
    block = int(ctx.mix["engine"].get("block_size", 16))
    params = dict(ctx.mix["params"])
    params["shared_prefixes"] = dict(params["shared_prefixes"],
                                     tokens=2 * block)
    prime(dataclasses.replace(ctx, mix=dict(ctx.mix, params=params)), eng, d)
    resident = make_resident(ctx, eng, work, d)
    if ctx.sabotage is not None:
        ctx.sabotage({"engine": eng})
    settle_heap()
    setup_s = time.perf_counter() - ctx.t_start
    result = window(ctx, eng, ref, d, work, setup_s, compiles)
    result["facts"]["resident"] = resident
    return result

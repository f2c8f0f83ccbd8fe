"""Driver of a serving cell (``kind: serve``).

Set-up builds the program's model, hands it the benchmark's seeded weights in
the type they are served in, builds the engine through
``inference.create_engine`` and warms the cell's own buckets.  The window
offers the mix's load from this one thread: an open loop submits each request
when it is due, whatever the engine is doing; a closed loop submits a
client's next request when its last completed.  Every token is stamped in
``stream_cb`` on the benchmark's clock.  After the window the requests in
flight are drained (not part of the window), the memory peak is read, and a
seeded sample of the finished requests, greedy and sampled in the mix's
shares and the longest among them, is held against the plain reference: one
full forward over prompt + served tokens, whose weights are made from the
seed again a layer at a time, so the program's state need not be freed and
the reference never holds the model.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.adapters import _load
from benchmarks.harness import stats, trace_reduce, weights
from benchmarks.harness.context import (Checks, CompileCounter, GcWatch,
                                        HostWatch, Spans, settle_heap)
from benchmarks.harness.manifest import load_module

ENGINE_DTYPE = "bfloat16"  # weights and KV as served, as the configurations state
DRAIN_CAP_S = 30.0       # the longest the requests in flight are waited for
TRACE_SECONDS = 3.0      # the traced slice (traces are large) ...
TRACE_BEFORE_END_S = 7.0  # ... starts this long before the window's end


class Record:
    """One request as the benchmark sees it."""

    __slots__ = ("spec", "due", "submitted", "stamps", "handle", "error")

    def __init__(self, spec, due):
        self.spec, self.due = spec, due
        self.submitted, self.stamps = None, []
        self.handle, self.error = None, None

    def on_token(self, _tok, _req):
        self.stamps.append(time.perf_counter())

    @property
    def finished(self):
        return self.handle is not None and self.handle.finished \
            and len(self.handle.output_ids) == self.spec["max_new_tokens"]


def build_engine(ctx, adapter, ref, d):
    import jax.numpy as jnp
    from paddle_tpu import inference

    import paddle_tpu as paddle

    paddle.seed(0)
    model = adapter.build_model(ctx.config)
    model.to(dtype=ENGINE_DTYPE)
    tree = weights.make(ref.weight_shapes(ctx.config), ctx.seed,
                        jnp.dtype(ENGINE_DTYPE))
    _load.load(model, adapter, tree, d)
    del tree
    eng = inference.create_engine(model, **ctx.mix["engine"])
    eng.warmup(buckets=ctx.mix.get("warmup_buckets"))
    return eng


def prime(ctx, eng, d) -> None:
    """Set-up's last step: a few short requests of every kind the mix sends
    (plain, sampled, two sharing a prefix so that the second hits the prefix
    cache) through the live engine, so that the small host-side programs the
    engine runs per request are compiled before the window.  Their tokens
    come from a stream of their own and share nothing with the window's."""
    from paddle_tpu.serving import SamplingParams

    p = ctx.mix["params"]
    rng = np.random.default_rng([int(ctx.seed), 0x7072696D])
    block = int(ctx.mix["engine"].get("block_size", 16))
    n_pre = int((p.get("shared_prefixes") or {}).get("tokens", 2 * block))
    prefix = rng.integers(0, d["vocab"], (n_pre,), dtype=np.int32)
    sa = p.get("sampled")
    kinds = [None, None] + ([SamplingParams(
        temperature=sa["temperature"], top_k=sa["top_k"], top_p=sa["top_p"],
        seed=1)] if sa else [])
    for sampling in kinds:
        tail = rng.integers(0, d["vocab"], (block + 3,), dtype=np.int32)
        # long enough to grow into a block the prompt did not reserve
        eng.add_request(np.concatenate([prefix, tail]),
                        max_new_tokens=block + 2, sampling=sampling)
        eng.run()


def submit(eng, rec: Record) -> None:
    from paddle_tpu.serving import SamplingParams

    s = rec.spec["sampling"]
    rec.submitted = time.perf_counter()
    try:
        rec.handle = eng.add_request(
            rec.spec["prompt"], max_new_tokens=rec.spec["max_new_tokens"],
            sampling=SamplingParams(**s) if s else None,
            stream_cb=rec.on_token)
    except (ValueError, RuntimeError) as e:     # refused at the door
        rec.error = f"{type(e).__name__}: {e}"


def step(eng, spans) -> None:
    """One ``engine.step()`` under a span that says how many prompts it
    admitted, how many slots ran after it, and how many cached tokens the
    running slots held before it."""
    kv = sum(int(r._seq_len) for r in eng.running.values())
    before = eng.metrics.requests_admitted
    with spans.span("engine.step", kv_tokens=kv,
                    running=len(eng.running)) as sp:
        eng.step()
    sp.attrs["admitted"] = eng.metrics.requests_admitted - before
    sp.attrs["busy"] = len(eng.running)
    sp.attrs["queued"] = len(eng.queue)     # left waiting: no slot was free
    al = getattr(getattr(eng, "cache", None), "allocator", None)
    if al is not None:
        sp.attrs["blocks"] = al.num_blocks - al.reserved - al.free_blocks


def offer(ctx, eng, work, spans, profiler):
    """Runs the window; returns ``(records, t0, t1)``."""
    reqs = work["requests"]
    closed = work["kind"] == "closed"
    if closed:
        lanes = [[Record(r, None) for r in reqs if r["client"] == c]
                 for c in range(work["clients"])]
        cursor = [0] * len(lanes)
        live = [None] * len(lanes)
        records = []
    else:
        records = [Record(r, r["due_s"]) for r in reqs]
        nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t0 >= ctx.seconds:
            break
        if profiler is not None:
            profiler.maybe(now)
        if closed:
            for c, lane in enumerate(lanes):
                if (live[c] is None or live[c].handle is None
                        or live[c].handle.done) and cursor[c] < len(lane):
                    live[c] = lane[cursor[c]]
                    cursor[c] += 1
                    with spans.span("submit"):
                        submit(eng, live[c])
                    records.append(live[c])
        else:
            while nxt < len(records) and records[nxt].due <= now - t0:
                with spans.span("submit"):
                    submit(eng, records[nxt])
                nxt += 1
        if eng.running or eng.queue:
            step(eng, spans)
        elif not closed and nxt < len(records):
            time.sleep(max(0.0, min(0.001, records[nxt].due - (now - t0))))
        else:
            time.sleep(0.001)
    t1 = time.perf_counter()
    if not closed:                  # due inside the window, however late
        for rec in records[nxt:]:
            submit(eng, rec)
    return records, t0, t1


def drain(eng, spans, cap_s: float) -> float:
    t = time.perf_counter()
    while (eng.running or eng.queue) and time.perf_counter() - t < cap_s:
        step(eng, spans)
    return time.perf_counter() - t


def reference_check(ctx, ref, d, sample, pad: int, checks):
    """Holds the served tokens of ``sample`` against the reference's logits:
    a greedy token against the reference's best at its position, a sampled
    token against the reference's ``top_k``-th best.  With ``ctx.control``
    also reads, at the same positions, the lower precision's own first choice
    and the worst token of its own top k."""
    import jax.numpy as jnp

    from benchmarks.references._common import rows_from

    shapes = ref.weight_shapes(ctx.config)
    dtype = jnp.dtype(ENGINE_DTYPE)

    def provider(names):
        return weights.make(shapes, ctx.seed, dtype, only=names)

    seqs, starts, outs = [], [], []
    for rec in sample:
        prompt = np.asarray(rec.spec["prompt"], np.int32)
        served = np.asarray(rec.handle.output_ids, np.int32)
        full = np.zeros(pad, np.int32)
        full[:len(prompt) + len(served)] = np.concatenate([prompt, served])
        seqs.append(full)
        starts.append(len(prompt) - 1)
        outs.append(served)
    rows = max(len(o) for o in outs)

    def logits(control):
        """Per sample: the reference's logits ``[len(served), vocab]`` at the
        positions that produced the served tokens."""
        hs = ref.hidden_many(provider, [jnp.asarray(s) for s in seqs], d,
                             control=control)
        head = provider(ref.HEAD_KEYS)
        out = []
        for h, st, served in zip(hs, starts, outs):
            lo = min(st, pad - rows)
            lg = np.asarray(ref.logits_rows(head, rows_from(h, lo, rows), d,
                                            control=control))
            out.append(lg[st - lo:st - lo + len(served)])
        return out

    def below(lg, tokens, k):
        """Per position: the reference's ``k``-th best logit minus its logit
        of ``tokens`` there."""
        kth = lg.max(axis=-1) if k == 1 else \
            np.partition(lg, -k, axis=-1)[:, -k]
        return kth - np.take_along_axis(lg, tokens, axis=-1).min(axis=-1)

    def top_k_of(rec):
        return 1 if rec.spec["sampling"] is None \
            else int(rec.spec["sampling"]["top_k"])

    t = time.perf_counter()
    ref_logits = logits(False)
    widest = {"served_logit_gap": None, "sampled_topk_gap": None}
    where, n_tok = {}, 0
    for i, (rec, lg, served) in enumerate(zip(sample, ref_logits, outs)):
        k = top_k_of(rec)
        name = "served_logit_gap" if k == 1 else "sampled_topk_gap"
        gaps = below(lg, served[:, None], k)
        n_tok += len(served)
        if widest[name] is None or not gaps.max() <= widest[name]:
            widest[name], where[name] = float(gaps.max()), \
                (i, int(gaps.argmax()))
    ctx.say(f"reference: {len(sample)} requests, {n_tok} served tokens in "
            f"{time.perf_counter() - t:.1f}s; widest gaps {widest} at "
            f"(sample, token) {where}")
    for name, value in widest.items():
        if value is not None:
            checks.le(name, value, float(ctx.limits[name]))
    control = None
    if ctx.control:
        control = {}
        for rec, lg, cl in zip(sample, ref_logits, logits(True)):
            k = top_k_of(rec)
            name = "served_logit_gap" if k == 1 else "sampled_topk_gap"
            pick = np.argpartition(cl, -k, axis=-1)[:, -k:]
            control[name] = max(control.get(name, -np.inf),
                                float(below(lg, pick, k).max()))
        ctx.say(f"control: widest gaps of what the lower precision puts "
                f"first / into its top k {control}")
    return widest, control, n_tok


def pick_sample(records, n: int, sampled_share: float, seed: int):
    """``n`` finished requests drawn from the seed, the longest of all among
    them: ``sampled_share`` of them sampled requests, the rest greedy.  A
    kind with too few finished requests gives what it has (and the count
    check then fails)."""
    done = [r for r in records if r.finished]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.spec["prompt"])
                  + r.spec["max_new_tokens"])
    rng = np.random.default_rng([int(seed), 0x636865636B])
    n_sampled = int(round(sampled_share * n))
    out = []
    for sampled, want in ((False, n - n_sampled), (True, n_sampled)):
        pool = [r for r in done if (r.spec["sampling"] is not None) == sampled]
        first = [r for r in pool if r is longest]
        rest = [r for r in pool if r is not longest]
        take = rng.permutation(len(rest))[:max(0, want - len(first))]
        out += (first + [rest[i] for i in take])[:want]
    return out


def window(ctx, eng, ref, d, work, setup_s: float, compiles):
    """Everything from the window's start: offers ``work``, drains, reads
    the memory peak, reduces the stamps to the end-to-end numbers and holds
    the sample against the reference.  Returns the driver's result."""
    checks, spans = Checks(ctx.say), Spans()
    compiles_before = compiles.count
    prefix0 = dict((eng.stats()["paging"] or {}).get("prefix") or {})
    profiler = None
    if ctx.trace:
        profiler = trace_reduce.Profiler(
            ctx.out_dir, time.perf_counter(),
            start_after=max(ctx.seconds / 3, ctx.seconds - TRACE_BEFORE_END_S),
            length=min(TRACE_SECONDS, ctx.seconds / 3))
    gc_watch, host_watch = GcWatch().start(), HostWatch().start()
    records, t0, t1 = offer(ctx, eng, work, spans, profiler)
    ctx.say(gc_watch.stop())
    ctx.say(host_watch.stop())
    trace = profiler.finish() if profiler is not None else None
    window_s = t1 - t0
    # a traced run's host clock is the program's only up to the profiler's
    # start: the per-layer readers take the window as far as that
    quiet = min(t1, profiler.t_on) if profiler is not None else t1
    st = eng.stats()
    prefix1 = dict((st["paging"] or {}).get("prefix") or {})
    in_window = compiles.count - compiles_before
    drain_s = drain(eng, spans, DRAIN_CAP_S)
    from benchmarks.harness import peaks as pk

    mem = pk.memory_peak_bytes()

    # -- what the window's users saw ---------------------------------------
    done_in = [r for r in records if r.finished and r.stamps[-1] <= t1]
    tokens_done = sum(len(r.stamps) for r in done_in)
    failed = [r for r in records if not r.finished]
    closed = work["kind"] == "closed"

    def users_saw(until: float, say) -> dict:
        """The host-clock numbers over the window up to ``until``: gaps that
        ended by then and, in an open loop, the time to first token of every
        request due a second or more before it (all of them when ``until``
        is the window's end); a request without a token counts as the
        window's length."""
        out = {}
        gaps = [b - a for r in records for a, b in zip(r.stamps, r.stamps[1:])
                if b <= until]
        if gaps:
            out["itl_mean_ms"] = 1e3 * sum(gaps) / len(gaps)
            out["itl_p50_ms"] = 1e3 * stats.percentile(gaps, 50)
            out["itl_p99_ms"] = 1e3 * stats.percentile(gaps, 99)
            # per request: the mean gap between its tokens stamped by then,
            # in flight or finished, so that none is left out
            tpot = [(xs[-1] - xs[0]) / (len(xs) - 1) for xs in (
                [s for s in r.stamps if s <= until] for r in records)
                if len(xs) > 1]
            out["tpot_p50_ms"] = 1e3 * stats.percentile(tpot, 50)
            say(f"itl ms p90 {1e3 * stats.percentile(gaps, 90):.3f} p95 "
                f"{1e3 * stats.percentile(gaps, 95):.3f} max "
                f"{1e3 * max(gaps):.1f} over {len(gaps)} gaps; tpot ms p90 "
                f"{1e3 * stats.percentile(tpot, 90):.3f} over {len(tpot)} "
                f"requests")
        if not closed:
            due = [r for r in records
                   if until >= t1 or t0 + r.due <= until - 1.0]
            ttft = [(r.stamps[0] - (t0 + r.due)) if r.stamps else window_s
                    for r in due]
            if ttft:
                out["ttft_p50_ms"] = 1e3 * stats.percentile(ttft, 50)
                out["ttft_mean_ms"] = 1e3 * sum(ttft) / len(ttft)
                out["ttft_p95_ms"] = 1e3 * stats.percentile(ttft, 95)
                say(f"ttft ms p90 {1e3 * stats.percentile(ttft, 90):.2f} "
                    f"max {1e3 * max(ttft):.2f} over {len(ttft)} requests")
        return out

    e2e = {"setup_s": setup_s, "serve_tokens_per_s": tokens_done / window_s,
           **users_saw(t1, ctx.say)}
    host_quiet = users_saw(quiet, lambda _msg: None) if quiet < t1 else e2e
    if not closed:
        late = [r.submitted - (t0 + r.due) for r in records]
        ctx.say(f"generator lateness ms: median "
                f"{1e3 * stats.percentile(late, 50):.2f} p99 "
                f"{1e3 * stats.percentile(late, 99):.2f}")
    inside = [(e - s, a) for name, s, e, a in spans.rows
              if name == "engine.step" and t0 <= s and e <= t1]
    in_engine = [a["busy"] + a["queued"] for _dt, a in inside]
    load = {
        "step_ms_max": 1e3 * max((dt for dt, _a in inside), default=0.0),
        "queued_max": max((a["queued"] for _dt, a in inside), default=0),
        "in_engine_max": max(in_engine, default=0),
        "in_engine_mean": sum(in_engine) / max(1, len(in_engine)),
        "pool_blocks_peak": max((a.get("blocks", 0) for _dt, a in inside),
                                default=0),
        "pool_blocks": (st["paging"] or {}).get("blocks"),
    }
    ctx.say(f"window {window_s:.3f}s: {len(records)} requests offered, "
            f"{len(done_in)} completed inside ({tokens_done} tokens), "
            f"drained {drain_s:.2f}s, not finished {len(failed)}, compiles "
            f"in window {in_window}, prefills by bucket "
            f"{st['prefills_by_bucket']}, engine failures {st['failures']}")
    ctx.say(f"load {load}")
    for r in failed[:3]:
        ctx.say(f"not finished: error "
                f"{r.error or getattr(r.handle, 'error', None)} "
                f"state {getattr(r.handle, 'state', None)}")

    if ctx.out_dir:
        import json
        import os

        steps = [(round(s - t0, 4), round(e - s, 5), a.get("admitted"),
                  a.get("busy")) for name, s, e, a in spans.rows
                 if name == "engine.step"]
        with open(os.path.join(ctx.out_dir, f"raw_{ctx.seed}.json"),
                  "w") as f:
            json.dump({"steps": steps, "e2e": e2e, "requests": [
                [r.due] + [round(s - t0, 5) for s in r.stamps]
                for r in records]}, f)

    # -- correct: the served tokens against the plain reference ------------
    want = int(ctx.mix["check_requests"])
    share = float((ctx.mix["params"].get("sampled") or {}).get("share", 0.0))
    sample = pick_sample(records, want, share, ctx.seed)
    checks.ge("checked_requests", len(sample), want)
    widest, control, n_tok = {}, None, 0
    if sample:
        widest, control, n_tok = reference_check(
            ctx, ref, d, sample, int(ctx.mix["reference_pad"]), checks)
    return {
        "checks": checks, "attempted": len(records), "failed": len(failed),
        "end_to_end": e2e, "host_quiet": host_quiet,
        "memory_peak_bytes": mem,
        "trace": trace, "spans": spans.rows,
        "counters": {"prefix_start": prefix0, "prefix_end": prefix1,
                     "stats": {k: st[k] for k in (
                         "requests", "failures", "tokens",
                         "prefills_by_bucket", "compile_cache")}},
        "facts": {"kind": "serve", "dims": d, "window": [t0, t1],
                  "quiet_window": [t0, quiet], "window_s": window_s,
                  "num_slots": int(ctx.mix["engine"]["num_slots"]),
                  "kv_itemsize": 2,
                  "completed_in_window": len(done_in),
                  "compiles_in_window": in_window, "drain_s": drain_s,
                  "load": load, "gaps": widest, "control_gaps": control,
                  "checked_tokens": n_tok},
    }


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
    prime(ctx, eng, d)
    if ctx.sabotage is not None:
        ctx.sabotage({"engine": eng})
    settle_heap()
    setup_s = time.perf_counter() - ctx.t_start
    return window(ctx, eng, ref, d, work, setup_s, compiles)

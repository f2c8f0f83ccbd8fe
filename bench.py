"""Benchmark: GPT-2 345M causal-LM training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
- value: tokens/sec/chip for the full compiled train step (fwd+bwd+AdamW)
  under bf16 autocast — config #2/#4 of BASELINE.md scaled to the single
  available chip.
- vs_baseline: achieved MFU / 0.45 (the north-star MFU target from
  BASELINE.json; the reference publishes no in-tree absolute numbers).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np


def _device_tags() -> dict:
    """The device every printed row is tagged with, as JAX reports it."""
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d)}


def fail_structured(msg: str,
                    metric: str = "gpt2_345m_train_tokens_per_sec_per_chip",
                    error_kind: str = "bench_failure", **extra):
    """One JSON line on stdout even on failure, then nonzero exit.
    ``error_kind`` classifies the failure machine-readably."""
    try:
        tags = _device_tags()
    except RuntimeError as e:            # the backend itself failed to start
        tags = {"platform": None, "device_error": str(e)}
    print(json.dumps({
        "metric": metric,
        "value": 0.0,
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "error": msg,
        "error_kind": error_kind,
        **tags,
        **extra,
    }))
    sys.exit(1)


def build_train_step(cfg, seq: int, hybrid_configs: dict = None):
    """The train step the bench times, for model config ``cfg`` at
    sequence length ``seq``: fleet.init → distributed_model → AdamW →
    AMP-O2 → ``@to_static`` step (bf16 autocast, streamed fused CE via
    ``model.compute_loss``).  ``hybrid_configs`` (e.g. ``{"dp_degree": 2,
    "mp_degree": 2}``) selects the parallel layout; default is pure data
    parallel over every device.

    Returns ``(make_step, model)``: ``make_step(batch) -> (train_step,
    x, y)``.  ``chip_smoke.py`` drives exactly this builder, so the
    smoke proves the program the bench measures.
    """
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.distributed import fleet

    strategy = paddle.distributed.DistributedStrategy()
    if hybrid_configs:
        strategy.hybrid_configs = hybrid_configs
    fleet.init(is_collective=True, strategy=strategy)

    paddle.seed(0)
    model = fleet.distributed_model(GPTForCausalLM(cfg))
    # opt-in experiment knob: bf16 moments halve AdamW HBM traffic
    # (~2.8 GB/step at 345M); default stays f32
    moment_dtype = os.environ.get("PADDLE_TPU_BENCH_ADAM_MOMENT_DTYPE") or None
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-4,
                               parameters=model.parameters(),
                               moment_dtype=moment_dtype))
    # O2 (bf16 params + f32 masters) is the BASELINE #3/#4 configuration
    amp_level = os.environ.get("PADDLE_TPU_BENCH_AMP", "O2")
    if amp_level == "O2":
        # bf16 params + f32 master weights in the optimizer: halves the
        # per-matmul weight HBM traffic vs O1's cast-on-use
        model, opt = paddle.amp.decorate(model, optimizers=opt, level="O2")

    rs = np.random.RandomState(0)

    def make_step(batch):
        @paddle.jit.to_static
        def train_step(x, y):
            with paddle.amp.auto_cast(dtype="bfloat16", level=amp_level):
                loss = model.compute_loss(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)))
        y = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)))
        return train_step, x, y

    return make_step, model


def build_bench(smoke: bool = False):
    """Create the EXACT model/optimizer/train-step main() times.

    Returns (make_step, cfg, seq, model): ``make_step(batch) ->
    (train_step, x, y)``.  Shared with tools/perf_fingerprint.py, which
    compiles (but does not run) the same program to fingerprint its HLO —
    keeping the fingerprint honest about what the bench really runs.
    """
    # Dropout 0 (standard MFU-bench practice; also engages the Pallas
    # flash kernel, whose dispatch guard requires p==0), recompute off
    # (345M + AdamW f32 state + flash-attn activations fit 16G HBM).  The
    # LM loss goes through model.compute_loss →
    # fused_linear_cross_entropy (vocab-blockwise streamed CE): no [B,S,V]
    # logits tensor is ever materialized, which un-caps the batch that
    # previously OOMed at 16 on the f32 logits temp.
    if smoke:
        # correctness smoke of the exact bench path on tiny shapes (CPU ok)
        from paddle_tpu.models import gpt_tiny

        cfg = gpt_tiny()
        seq = 32
    else:
        from paddle_tpu.models import gpt2_345m

        cfg = gpt2_345m(recompute=False, hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        seq = 1024
    make_step, model = build_train_step(cfg, seq)
    return make_step, cfg, seq, model


def _trace_replay(model):
    """Overload trace-replay bench (ISSUE 8): seeded Poisson arrivals of
    mixed priorities, prompt lengths, and output budgets are replayed
    against a paged priority engine — and then against an identical
    engine with every request forced to one class (the no-priority
    baseline).  Emits p50/p99 TTFT and ITL under load plus the
    preemption/shed counters, and enforces the ISSUE 8 acceptance
    criteria: every request reaches a terminal state exactly once, the
    steady state adds zero compile misses in BOTH runs (preemption and
    resume reuse the warmed prefill buckets), and high-priority p99 TTFT
    under overload beats the no-priority baseline.

    The measured (priorities-on) run additionally carries a
    ``RequestTracer`` (ISSUE 9): after the run the span-chain validator
    must pass — every request's chain closed exactly once, preemption
    spans linked parent→child — and the chain must render into a
    Perfetto-loadable Chrome trace, emitted as ``serving_trace_events``
    / ``serving_trace_valid`` (written to
    ``$PADDLE_TPU_TRACE_DIR/serving_trace.json`` when set).  The traced
    run reuses the same zero-compile-miss assertion, proving tracing
    adds no steady-state compile and no new cache keys."""
    import time as _time

    import numpy as np
    from paddle_tpu import obs
    from paddle_tpu.serving import (Engine, NULL_TRACER, QueueFull,
                                    RequestTracer, validate_trace)

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    rs = np.random.RandomState(42)
    n = 28
    arrivals = np.cumsum(rs.exponential(scale=0.003, size=n))
    lengths = rs.randint(3, 44, size=n)
    prompts = [rs.randint(0, 128, (int(L),)).tolist() for L in lengths]
    max_new = rs.choice([8, 12, 16], size=n)
    # deterministic mixed classes: high riding mid-trace so it always
    # lands on a saturated engine; low/normal interleaved
    prios = [2 if i % 7 == 3 else (0 if i % 3 == 0 else 1)
             for i in range(n)]
    # two doomed stragglers at the tail exercise SLO shedding: by their
    # arrival the estimator has ITL history and a deep backlog, so a
    # 2 ms deadline is hopeless and must be shed, not prefilled
    doomed = [rs.randint(0, 128, (8,)).tolist() for _ in range(2)]

    def run(priorities_on):
        # lifecycle tracing rides the MEASURED run only; the baseline is
        # pinned to the no-op tracer (NOT None, which would fall back to
        # the env-armed tracer under PADDLE_TPU_TRACE=1 and skew the
        # priority-vs-baseline TTFT comparison)
        tracer = RequestTracer() if priorities_on else NULL_TRACER
        eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                     block_size=8, tracer=tracer)
        eng.warmup()
        t0 = _time.perf_counter()
        handles = []
        for i in range(n):
            while _time.perf_counter() - t0 < arrivals[i]:
                eng.step()
            handles.append(eng.add_request(
                prompts[i], max_new_tokens=int(max_new[i]),
                priority=prios[i] if priorities_on else 1))
        for p in doomed:
            try:
                handles.append(eng.add_request(
                    p, max_new_tokens=4, deadline_s=0.002,
                    priority=0 if priorities_on else 1))
            except QueueFull as e:       # ShedReject included
                handles.append(e.request)
        eng.run()
        st = eng.stats()
        if st["compile_cache"]["misses"] != len(eng.buckets) + 1:
            fail_structured(
                f"trace-replay recompile (priorities_on="
                f"{priorities_on}): {st['compile_cache']}",
                metric=FAIL_METRIC)
        if any(not r.done for r in handles) or \
                len(handles) != n + len(doomed):
            fail_structured(
                f"trace-replay left non-terminal requests "
                f"(priorities_on={priorities_on}): "
                f"{[(r.state, r.error) for r in handles if not r.done]}",
                metric=FAIL_METRIC)
        if st["health"]["state"] != "active" or \
                st["health"]["kv_block_invariants"] != "ok":
            fail_structured(f"trace-replay engine unhealthy: "
                            f"{st['health']}", metric=FAIL_METRIC)
        return st, handles, tracer

    st_p, h_p, tracer = run(True)
    st_b, h_b, _ = run(False)

    # -- ISSUE 9: the measured run's span chain must validate and render
    problems = validate_trace(tracer)
    if problems:
        fail_structured("trace-replay span chain invalid: "
                        + "; ".join(problems[:5]), metric=FAIL_METRIC)
    chrome = obs.chrome_trace(tracer)
    if not chrome["traceEvents"] or chrome["metadata"]["dropped"]:
        fail_structured(f"trace-replay chrome export degenerate: "
                        f"{chrome['metadata']}", metric=FAIL_METRIC)
    json.dumps(chrome)                   # Perfetto loads plain JSON
    trace_dir = os.environ.get("PADDLE_TPU_TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        obs.write_chrome_trace(
            tracer, os.path.join(trace_dir, "serving_trace.json"))

    def q(xs, p):
        s = sorted(xs)
        return s[min(len(s) - 1, int(p * (len(s) - 1) + 0.5))]

    hi = [i for i in range(n) if prios[i] == 2]
    tp = [h_p[i].ttft_s for i in hi if h_p[i].finished]
    tb = [h_b[i].ttft_s for i in hi if h_b[i].finished]
    if not tp or not tb:
        fail_structured("trace-replay high-priority class produced no "
                        "finished requests", metric=FAIL_METRIC)
    hi_p99_p, hi_p99_b = q(tp, 0.99) * 1e3, q(tb, 0.99) * 1e3
    if hi_p99_p >= hi_p99_b:
        fail_structured(
            f"priority scheduling did not beat the no-priority baseline "
            f"under overload: high-prio p99 TTFT {hi_p99_p:.1f}ms >= "
            f"baseline {hi_p99_b:.1f}ms", metric=FAIL_METRIC)
    if st_p["overload"]["preemptions"] < 1:
        fail_structured("overload trace triggered no preemption",
                        metric=FAIL_METRIC)
    if st_p["overload"]["shed"] < 1:
        fail_structured("overload trace shed no doomed request",
                        metric=FAIL_METRIC)
    return {
        "serving_ttft_p50_ms": st_p["ttft_ms"]["p50"],
        "serving_ttft_p99_ms": st_p["ttft_ms"]["p99"],
        "serving_itl_p50_ms": st_p["inter_token_ms"]["p50"],
        "serving_itl_p99_ms": st_p["inter_token_ms"]["p99"],
        "serving_preemptions": st_p["overload"]["preemptions"],
        "serving_shed": st_p["overload"]["shed"],
        "serving_high_ttft_p50_ms": round(q(tp, 0.5) * 1e3, 3),
        "serving_high_ttft_p99_ms": round(hi_p99_p, 3),
        "serving_baseline_high_ttft_p50_ms": round(q(tb, 0.5) * 1e3, 3),
        "serving_baseline_high_ttft_p99_ms": round(hi_p99_b, 3),
        # lifecycle tracing (ISSUE 9): the measured run's event count
        # and the chain-validator verdict (1.0 = every request's span
        # chain closed exactly once, preempt links intact, Perfetto
        # export well-formed) — the traced run passed the same
        # zero-compile-miss gate above, so tracing provably added no
        # steady-state compiles
        "serving_trace_events": len(tracer.events),
        "serving_trace_valid": 1.0,
    }


def _paged_kernel_microbench(model):
    """Paged-kernel vs reference-gather decode microbench (ISSUE 11):
    the same decode-heavy workload through two paged engines that differ
    ONLY in the attention path — ``kernel="pallas"`` (block table
    consumed inside the flash-decoding kernel) vs ``kernel="reference"``
    (jnp gather + masked softmax).  Greedy outputs must agree bitwise
    and both runs must stay at zero steady-state compile misses; the
    throughput ratio is emitted as ``serving_paged_kernel_speedup`` so
    the trajectory is tracked even off-TPU (in Pallas interpret mode the
    kernel pays an interpreter tax the XLA-native gather doesn't; only
    a chip run says anything about the compiled kernel, which also skips
    the materialized contiguous K/V copy)."""
    import numpy as np
    from paddle_tpu.serving import Engine

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in (9, 17, 30, 5)]
    tps, outs = {}, {}
    for kern in ("pallas", "reference"):
        eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                     block_size=8, kernel=kern)
        eng.warmup()
        eng.generate(prompts, max_new_tokens=4)     # prime steady state
        reqs = [eng.add_request(p, max_new_tokens=24) for p in prompts]
        eng.run()
        st = eng.stats()
        if st["compile_cache"]["misses"] != len(eng.buckets) + 1:
            fail_structured(
                f"paged {kern} kernel path recompiled in steady state: "
                f"{st['compile_cache']}", metric=FAIL_METRIC)
        if any(not r.finished for r in reqs):
            fail_structured(f"paged {kern} microbench left unfinished "
                            "requests", metric=FAIL_METRIC)
        outs[kern] = [r.output_ids for r in reqs]
        tps[kern] = st["decode_tokens_per_sec"]
    if outs["pallas"] != outs["reference"]:
        fail_structured("paged kernel greedy outputs diverge from the "
                        "reference-gather path", metric=FAIL_METRIC)
    return {
        "serving_paged_kernel_tokens_per_sec": round(tps["pallas"], 2),
        "serving_paged_reference_tokens_per_sec":
            round(tps["reference"], 2),
        "serving_paged_kernel_speedup":
            round(tps["pallas"] / max(tps["reference"], 1e-9), 4),
    }


def _spec_decode_drill(model):
    """Speculative-decoding drill (ISSUE 15): the same greedy workload
    through a plain paged engine and a speculative one (tiny 1-layer
    independent draft + the small target, ``k=4``).  Greedy outputs
    must agree BITWISE (every emitted speculative token is the target
    argmax at its position, whatever the draft proposed), both modes
    must hold zero steady-state compile misses, and the acceptance
    machinery must actually fire (``serving_spec_accept_rate`` > 0).
    The tokens/sec pair is the tracked trajectory: on CPU with a
    random-weight draft the acceptance rate prices the draft overhead
    honestly (~30% acceptance); the multiplicative
    win arrives with a distilled draft on real hardware, where k
    accepted tokens cost one target-window forward instead of k
    sequential target steps."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, SpecConfig

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    paddle.seed(17)
    draft = GPTForCausalLM(GPTConfig(
        vocab_size=model.config.vocab_size, hidden_size=32,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=model.config.max_position_embeddings,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in (7, 15, 26, 4)]
    runs = {}
    for mode in ("nospec", "spec"):
        kw = {} if mode == "nospec" else dict(
            speculation=SpecConfig(draft_model=draft, k=4))
        eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                     block_size=8, **kw)
        eng.warmup()
        eng.generate(prompts, max_new_tokens=4)     # prime steady state
        m0 = eng.metrics.compile_misses
        reqs = [eng.add_request(p, max_new_tokens=24) for p in prompts]
        eng.run()
        st = eng.stats()
        if eng.metrics.compile_misses != m0:
            fail_structured(
                f"speculative drill ({mode}) recompiled in steady "
                f"state: {st['compile_cache']}", metric=FAIL_METRIC)
        if any(not r.finished for r in reqs):
            fail_structured(
                f"speculative drill ({mode}) left unfinished requests",
                metric=FAIL_METRIC)
        runs[mode] = ([r.output_ids for r in reqs], st)
    if runs["spec"][0] != runs["nospec"][0]:
        fail_structured("speculative greedy outputs diverge from the "
                        "non-speculative run", metric=FAIL_METRIC)
    st = runs["spec"][1]
    sp = st["speculation"]
    if not sp["rounds"] or sp["accept_rate"] <= 0.0:
        fail_structured(
            f"speculative drill accepted nothing: {sp}",
            metric=FAIL_METRIC)
    return {
        "serving_spec_accept_rate": sp["accept_rate"],
        "serving_spec_tokens_per_round": round(
            st["tokens"]["decode"] / sp["rounds"], 4),
        "serving_spec_tokens_per_sec": st["decode_tokens_per_sec"],
        "serving_nospec_tokens_per_sec":
            runs["nospec"][1]["decode_tokens_per_sec"],
    }


def _multi_tenant_drill(model):
    """Multi-tenant serving drill (ISSUE 20): ONE paged engine serving
    a heterogeneous seeded-Poisson mix of four tenant classes — base,
    two LoRA adapters, and JSON-grammar-constrained — through the SAME
    warmed executables.  Enforced structurally: zero steady-state
    compile misses across the whole mix (adapter ids and grammar states
    are data, never trace constants), zero cross-tenant prefix hits
    (per-adapter cache salts keep an identical prompt's KV disjoint
    between tenants), and ``serving_grammar_valid_rate == 1.0`` (every
    grammar-class output parses).  Emits per-class TTFT p50/p99 and the
    adapter hot-swap latency."""
    import time as _time

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.serving import (
        Engine, JsonArrayGrammar, SamplingParams, make_lora_weights,
    )

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    spec = JsonArrayGrammar(eos_token_id=1, max_elems=3, max_digits=2)
    eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                 block_size=8, adapters=dict(max_adapters=2, rank=4),
                 grammars={"json": spec})
    eng.warmup()
    pool = eng.adapter_pool
    eng.load_adapter("tenant-a",
                     make_lora_weights(pool, seed=1, init_scale=0.5))
    eng.load_adapter("tenant-b",
                     make_lora_weights(pool, seed=2, init_scale=0.5))

    CLASSES = ("base", "tenant-a", "tenant-b", "json")

    def _params(cls):
        if cls == "json":
            return dict(max_new_tokens=spec.max_tokens,
                        sampling=SamplingParams(grammar="json"))
        if cls == "base":
            return dict(max_new_tokens=12)
        return dict(max_new_tokens=12,
                    sampling=SamplingParams(adapter=cls))

    rs = np.random.RandomState(23)
    # one SHARED prompt every class submits (the cross-tenant prefix
    # trap: identical bytes, four disjoint salt domains) plus
    # per-request random prompts
    shared = rs.randint(0, 128, (24,)).tolist()
    # prime steady state: one request per class, then counters must
    # stay flat for the whole mixed run
    for cls in CLASSES:
        eng.add_request(rs.randint(0, 128, (9,)).tolist(), **_params(cls))
    eng.run()

    # cross-tenant prefix isolation, probed structurally BEFORE the mix:
    # the shared prompt registered under base's (unsalted) domain must
    # be invisible under every adapter's salt — identical bytes, four
    # disjoint hash domains
    eng.add_request(list(shared), max_new_tokens=4)
    eng.run()
    if not eng.prefix_probe(shared):
        fail_structured("multi-tenant drill: shared prompt never "
                        "registered in the prefix cache",
                        metric=FAIL_METRIC)
    for a in ("tenant-a", "tenant-b"):
        if eng.prefix_probe(shared, adapter=a):
            fail_structured(
                f"CROSS-TENANT PREFIX HIT: adapter {a!r} sees KV "
                "registered under the base domain — the per-adapter "
                "cache salt is broken", metric=FAIL_METRIC)
    m0 = eng.metrics.compile_misses
    h0 = eng.stats()["paging"]["prefix"]["hit_blocks"]

    # heterogeneous Poisson arrivals, measured in engine steps so the
    # drill is seeded-deterministic: each step admits k ~ Poisson(0.7)
    # new requests of a seeded class mix until the budget is spent
    N = 24
    plan = [(CLASSES[rs.randint(len(CLASSES))],
             shared if rs.rand() < 0.3
             else rs.randint(0, 128, (int(rs.randint(4, 28)),)).tolist())
            for _ in range(N)]
    reqs, by_class, i = [], {c: [] for c in CLASSES}, 0
    while i < N or any(not r.finished for r in reqs):
        for _ in range(int(rs.poisson(0.7))):
            if i >= N:
                break
            cls, prompt = plan[i]
            r = eng.add_request(list(prompt), **_params(cls))
            reqs.append(r)
            by_class[cls].append(r)
            i += 1
        eng.step()
    if any(not r.finished for r in reqs):
        fail_structured("multi-tenant drill left unfinished requests",
                        metric=FAIL_METRIC)
    st = eng.stats()
    if eng.metrics.compile_misses != m0:
        fail_structured(
            f"multi-tenant drill recompiled in steady state: "
            f"{st['compile_cache']} (adapter/grammar lanes must be "
            "data, not trace constants)", metric=FAIL_METRIC)

    # same-tenant reuse must still WORK: the shared prompt was
    # submitted repeatedly, so the run must have produced real hits
    if st["paging"]["prefix"]["hit_blocks"] <= h0:
        fail_structured("multi-tenant drill produced no same-tenant "
                        "prefix hits (the reuse path went dead)",
                        metric=FAIL_METRIC)

    valid = [1.0 if spec.accepts(r.output_ids, model.config.vocab_size)
             else 0.0 for r in by_class["json"]]
    valid_rate = (sum(valid) / len(valid)) if valid else 1.0
    if valid_rate != 1.0:
        fail_structured(
            f"grammar-constrained outputs invalid: valid_rate="
            f"{valid_rate} of {len(valid)}", metric=FAIL_METRIC)

    # adapter hot-swap latency: re-load tenant-a (new weights, same
    # lane) on the now-idle engine — the ms an operator pays per swap
    t0 = _time.perf_counter()
    eng.load_adapter("tenant-a",
                     make_lora_weights(pool, seed=3, init_scale=0.5))
    swap_ms = (_time.perf_counter() - t0) * 1e3

    def q(xs, p):
        s = sorted(xs)
        return s[min(len(s) - 1, int(p * (len(s) - 1) + 0.5))]

    out = {"serving_adapter_swap_ms": round(swap_ms, 3),
           "serving_grammar_valid_rate": valid_rate}
    for cls, label in (("base", "base"), ("tenant-a", "lora_a"),
                       ("tenant-b", "lora_b"), ("json", "json")):
        ts = [r.ttft_s for r in by_class[cls]]
        if not ts:           # seeded plan guarantees non-empty classes
            fail_structured(f"multi-tenant drill class {cls} drew no "
                            "requests", metric=FAIL_METRIC)
        out[f"serving_tenant_{label}_ttft_p50_ms"] = round(
            q(ts, 0.5) * 1e3, 3)
        out[f"serving_tenant_{label}_ttft_p99_ms"] = round(
            q(ts, 0.99) * 1e3, 3)
    return out


def _durability_drill(model):
    """Crash-recovery drill (ISSUE 14): an engine journals live traffic
    into a :class:`RequestJournal` and is ABANDONED mid-decode (the
    in-process stand-in for the SIGKILL drill tests/test_durability.py
    runs as a real subprocess); a fresh engine re-scans the journal,
    ``recover()``-s every non-terminal request, and must finish them
    all — terminal exactly once (``duplicate_terminals == 0``), zero
    steady-state compile misses, nothing lost.  Emits the measured
    ``serving_recovery_ms`` (recover + replay-to-completion wall time)
    and ``serving_journal_replayed``."""
    import tempfile
    import time as _time

    import numpy as np
    from paddle_tpu.serving import Engine, RequestJournal

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    with tempfile.TemporaryDirectory() as td:
        jdir = os.path.join(td, "journal")
        eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                     block_size=8, journal=RequestJournal(jdir))
        eng.warmup()
        rs = np.random.RandomState(123)
        prompts = [rs.randint(0, 128, (L,)).tolist()
                   for L in (5, 12, 9, 17, 7, 21)]
        for p in prompts:
            eng.add_request(p, max_new_tokens=10)
        for _ in range(4):
            eng.step()                   # tokens streamed, then "crash"

        j2 = RequestJournal(jdir)        # fresh-process view: re-scan
        if not j2.pending():
            fail_structured("durability drill: nothing was in flight "
                            "at the crash point", metric=FAIL_METRIC)
        eng2 = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                      block_size=8, journal=j2)
        eng2.warmup()
        misses0 = eng2.metrics.compile_misses
        t0 = _time.perf_counter()
        info = eng2.recover()
        eng2.run()
        recovery_ms = (_time.perf_counter() - t0) * 1e3
        audit = j2.audit()
        if audit["pending"] or audit["duplicate_terminals"] or \
                any(r.state != "finished" for r in info["requests"]):
            fail_structured(
                f"durability drill lost a request: {audit}, states="
                f"{[r.state for r in info['requests']]}",
                metric=FAIL_METRIC)
        if eng2.metrics.compile_misses != misses0:
            fail_structured(
                "crash recovery added steady-state compile misses",
                metric=FAIL_METRIC)
        # close (and unregister) both journal handles: the tempdir dies
        # with this with-block, and a stale registration would hijack
        # crash_dir() for the rest of the bench process
        eng.journal.close()
        j2.close()
        return {
            "serving_recovery_ms": round(recovery_ms, 3),
            "serving_journal_replayed": info["replayed"],
        }


def _hot_swap_drill(model):
    """Rolling weight hot-swap drill (ISSUE 14): a 2-replica paged
    fleet serves live streams while ``Fleet.update_weights`` drains and
    swaps one replica at a time (weight isolation: the other replica
    keeps answering on the old weights).  Fails structured unless every
    request — in-flight across the roll AND submitted after — finishes,
    no replica adds an executable-cache key, and no post-roll admission
    prefix-hits a block prefilled under the old weights (the version
    epoch).  Emits ``serving_hot_swap_stall_ms``: the worst per-request
    inter-token gap observed across the roll — the number a
    zero-downtime claim lives or dies on."""
    import time as _time

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import Fleet

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    paddle.seed(31)
    new_sd = GPTForCausalLM(gpt_tiny()).state_dict()
    fleet = Fleet(model, num_replicas=2, num_slots=2, max_seq=64,
                  min_bucket=8, block_size=8)
    fleet.warmup()
    if not fleet.weights_isolated:
        fail_structured("hot-swap drill: fleet fell back to shared "
                        "weights", metric=FAIL_METRIC)
    gaps, last = {}, {}

    def cb(tok, fr):
        now = _time.perf_counter()
        if fr.request_id in last:
            gaps[fr.request_id] = max(gaps.get(fr.request_id, 0.0),
                                      now - last[fr.request_id])
        last[fr.request_id] = now

    rs = np.random.RandomState(99)
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in (6, 13, 9, 18)]
    live = [fleet.submit(p, max_new_tokens=16, stream_cb=cb)
            for p in prompts]
    for _ in range(2):
        fleet.step()                     # streams flowing on both replicas
    misses = {rep.engine.name: rep.engine.metrics.compile_misses
              for rep in fleet.replicas}
    roll = fleet.update_weights(new_sd, max_drain_steps=2000)
    hits_at_roll = sum(rep.engine.prefix_cache.hit_tokens_total
                       for rep in fleet.replicas)
    post = [fleet.submit(p, max_new_tokens=8, stream_cb=cb)
            for p in prompts[:2]]        # the SAME prompts, post-swap
    fleet.run()
    st = fleet.stats()
    if any(r.state != "finished" for r in live + post) or \
            st["requests"]["failed"] or \
            st["requests"]["duplicate_terminals"]:
        fail_structured(
            f"hot swap dropped traffic: {st['requests']}, states="
            f"{[r.state for r in live + post]}", metric=FAIL_METRIC)
    for rep in fleet.replicas:
        if rep.engine.metrics.compile_misses != misses[rep.engine.name]:
            fail_structured(
                f"hot swap added compile keys on {rep.engine.name}",
                metric=FAIL_METRIC)
    hits_after = sum(rep.engine.prefix_cache.hit_tokens_total
                     for rep in fleet.replicas)
    if hits_after != hits_at_roll:
        fail_structured(
            "post-roll admission prefix-hit blocks prefilled under the "
            "old weights (version epoch breached)", metric=FAIL_METRIC)
    if any(r.model_version != 0 for r in live) or \
            any(r.model_version != 1 for r in post):
        fail_structured(
            "model-version tagging wrong across the roll",
            metric=FAIL_METRIC)
    fleet.shutdown(timeout_s=0.0)
    return {
        "serving_hot_swap_stall_ms":
            round(max(gaps.values()) * 1e3, 3) if gaps else 0.0,
        "serving_hot_swap_roll_ms": roll["roll_ms"],
        "serving_hot_swap_model_version": roll["model_version"],
    }


def _sharded_serving_drill_child():
    """Child half of the sharded serving drill
    (``--sharded-serving-drill``): on the 8-device virtual CPU mesh,
    serve the same workload through a single-chip paged engine and a
    model=2 tensor-parallel paged engine (``Engine(mesh=...)``), and
    print one JSON line with greedy output parity, the sharded engine's
    steady-state compile misses, and both decode throughputs."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import Engine, serving_mesh

    def build():
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        m.eval()
        return m

    rs = np.random.RandomState(0)
    lengths = [5, 13, 21, 34, 9, 17, 48, 3, 27, 11, 40, 6]
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in lengths]
    kw = dict(num_slots=4, max_seq=64, min_bucket=8, block_size=8)

    base = Engine(build(), **kw)
    base.warmup()
    want = base.generate(prompts, max_new_tokens=12)
    base_tps = base.stats()["decode_tokens_per_sec"]

    eng = Engine(build(), mesh=serving_mesh(2), **kw)
    eng.warmup()
    warm = eng.metrics.compile_misses
    got = eng.generate(prompts, max_new_tokens=12)
    st = eng.stats()
    print(json.dumps({
        "match": 1.0 if got == want else 0.0,
        "steady_misses": eng.metrics.compile_misses - warm,
        "sharded_tokens_per_sec": st["decode_tokens_per_sec"],
        "baseline_tokens_per_sec": base_tps,
        "mesh_shape": st["sharding"]["mesh_shape"],
        "model_parallel": st["sharding"]["model_parallel"],
        "engine_state": st["health"]["state"],
    }))


def _sharded_serving_drill():
    """Tensor-parallel serving drill (ISSUE 18): run the 2-shard-vs-
    single-chip comparison in a subprocess pinned to the virtual CPU
    mesh (the parent may hold a single-device backend), and fail the
    bench structured on any greedy output divergence or steady-state
    compile miss.  The throughput pair is the honest CPU statement: two
    host devices emulating one chip each price the per-layer TP
    all-reduces in, so the sharded number trails the single-chip one
    off-hardware — the tracked contract is bitwise parity at zero
    steady-state recompiles per mesh shape."""
    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        env["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    env.pop("PADDLE_TPU_BENCH_SMOKE", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--sharded-serving-drill"],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        fail_structured("sharded serving drill crashed: "
                        + (proc.stderr or proc.stdout)[-800:],
                        metric=FAIL_METRIC)
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    if not lines:
        fail_structured(f"sharded serving drill emitted no JSON: "
                        f"{proc.stdout[-400:]!r}", metric=FAIL_METRIC)
    d = json.loads(lines[-1])
    if d["match"] != 1.0:
        fail_structured(
            "sharded greedy outputs diverge from the single-chip "
            "engine", metric=FAIL_METRIC)
    if d["steady_misses"]:
        fail_structured(
            f"sharded engine recompiled in steady state: "
            f"{d['steady_misses']} misses", metric=FAIL_METRIC)
    if d["engine_state"] != "active":
        fail_structured(
            f"sharded engine unhealthy after the drill: "
            f"{d['engine_state']}", metric=FAIL_METRIC)
    return {
        "serving_sharded_tokens_per_sec": d["sharded_tokens_per_sec"],
        "serving_sharded_mesh_shape": d["mesh_shape"],
        "serving_sharded_vs_single_chip": round(
            d["sharded_tokens_per_sec"]
            / max(d["baseline_tokens_per_sec"], 1e-9), 4),
    }


def _degraded_serving_serve_child():
    """Serve half of the kill-a-shard drill
    (``--degraded-serving-serve-child <journal_dir>``): a model=2
    tensor-parallel engine journals live STREAMING traffic on the
    8-device virtual CPU mesh, then SIGKILLs its own process mid-decode
    — the honest stand-in for a shard host dying under load."""
    import signal

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import (
        Engine, RequestJournal, SamplingParams, serving_mesh,
    )

    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    eng = Engine(m, mesh=serving_mesh(2), num_slots=2, max_seq=32,
                 min_bucket=8, block_size=8,
                 journal=RequestJournal(sys.argv[-1]))
    eng.warmup()
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in (6, 11, 14)]
    streamed = []
    eng.add_request(prompts[0], max_new_tokens=8,
                    stream_cb=lambda r, t: streamed.append(t))
    eng.add_request(prompts[1], max_new_tokens=8,
                    sampling=SamplingParams(temperature=0.7, top_k=8,
                                            seed=99),
                    stream_cb=lambda r, t: streamed.append(t))
    eng.add_request(prompts[2], max_new_tokens=8,
                    stream_cb=lambda r, t: streamed.append(t))
    steps = 0
    while eng.step():
        steps += 1
        if steps == 3:              # mid-decode, tokens already streamed
            print(f"STREAMED {len(streamed)}", flush=True)
            print("KILLING", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    raise SystemExit("unreachable: the SIGKILL must land mid-drill")


def _degraded_serving_recover_child():
    """Recovery half of the kill-a-shard drill
    (``--degraded-serving-recover-child <journal_dir>``): the SIGKILL'd
    host took mesh device 1 with it — carve the largest viable mp' on
    the SURVIVING device (``degrade_step``), replay the journal
    cross-mesh onto the rebuilt group, and print one JSON line with the
    bitwise verdict against an uninterrupted oracle run at the degraded
    shape, the rebuild+replay wall time, and the exactly-once audit."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import (
        Engine, RequestJournal, SamplingParams, serving_mesh,
    )
    from paddle_tpu.serving.sharding import degrade_step

    j = RequestJournal(sys.argv[-1])
    pend = j.pending()

    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    # the serve child ran mp=2 on devices[:2]; the kill lost device 1
    survivors = [jax.devices()[0]]
    new_mp = degrade_step(4, 4, len(survivors))
    t0 = time.perf_counter()
    eng = Engine(m, mesh=serving_mesh(new_mp, devices=survivors),
                 num_slots=2, max_seq=32, min_bucket=8, block_size=8)
    eng.warmup()
    rebuild_s = time.perf_counter() - t0

    # uninterrupted oracle at the DEGRADED shape, rebuilt from the
    # journaled replay recipes (seed_effective included) — runs
    # unjournaled so the exactly-once audit spans only real traffic
    refs = []
    for jid, ad in pend.items():
        s = dict(ad["sampling"])
        if s.get("seed") is None:
            s["seed"] = ad["seed_effective"]
        refs.append(eng.add_request(ad["prompt_ids"],
                                    max_new_tokens=ad["max_new_tokens"],
                                    sampling=SamplingParams(**s)))
    eng.run()

    misses0 = eng.metrics.compile_misses
    t1 = time.perf_counter()
    info = eng.recover(j)
    eng.run()
    rebuild_s += time.perf_counter() - t1
    rec = info["requests"]
    a = j.audit()
    print(json.dumps({
        "pending": len(pend),
        "replayed": info["replayed"],
        "cross_mesh": info["cross_mesh"],
        "lost": len(pend) - sum(1 for r in rec
                                if r.state == "finished"),
        "match": 1.0 if [r.output_ids for r in rec]
        == [r.output_ids for r in refs] else 0.0,
        "steady_misses": eng.metrics.compile_misses - misses0,
        "rebuild_ms": round(rebuild_s * 1e3, 3),
        "model_parallel": new_mp,
        "mesh_shape": eng.mesh_shape,
        "duplicate_terminals": a["duplicate_terminals"],
        "mesh_reshards": a["mesh_reshards"],
        "engine_state": eng.stats()["health"]["state"],
    }))


def _degraded_serving_drill():
    """Kill-a-shard drill (ISSUE 19): SIGKILL a model=2 serving process
    mid-decode with streaming requests in flight, then rebuild the
    group at the largest viable mp' on the surviving device and replay
    the journal cross-mesh.  Fails structured unless the child died BY
    SIGKILL, every journaled request came back terminal exactly once
    (``lost == 0``), the replayed greedy/seeded outputs are bitwise
    identical to an uninterrupted oracle at the degraded shape, and the
    rebuilt group ran at zero steady-state recompiles."""
    import signal
    import tempfile

    FAIL_METRIC = "serving_gpt_tiny_decode_tokens_per_sec"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        env["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    env.pop("PADDLE_TPU_BENCH_SMOKE", None)
    jdir = tempfile.mkdtemp(prefix="degraded_drill_")
    serve = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--degraded-serving-serve-child", jdir],
        capture_output=True, text=True, env=env, timeout=600)
    if serve.returncode != -signal.SIGKILL:
        fail_structured(
            f"kill-a-shard drill: serve child did not die by SIGKILL "
            f"(rc={serve.returncode}): "
            + (serve.stderr or serve.stdout)[-800:],
            metric=FAIL_METRIC)
    if "KILLING" not in serve.stdout:
        fail_structured("kill-a-shard drill: child exited before the "
                        "scripted SIGKILL", metric=FAIL_METRIC)
    recover = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--degraded-serving-recover-child", jdir],
        capture_output=True, text=True, env=env, timeout=600)
    if recover.returncode != 0:
        fail_structured("kill-a-shard drill: recovery child crashed: "
                        + (recover.stderr or recover.stdout)[-800:],
                        metric=FAIL_METRIC)
    lines = [ln for ln in recover.stdout.strip().splitlines()
             if ln.startswith("{")]
    if not lines:
        fail_structured(f"kill-a-shard drill emitted no JSON: "
                        f"{recover.stdout[-400:]!r}",
                        metric=FAIL_METRIC)
    d = json.loads(lines[-1])
    if d["lost"] != 0:
        fail_structured(
            f"kill-a-shard drill lost {d['lost']} of {d['pending']} "
            f"journaled requests across the degradation",
            metric=FAIL_METRIC)
    if d["match"] != 1.0:
        fail_structured(
            "kill-a-shard drill: cross-mesh replay diverges from the "
            "uninterrupted oracle at the degraded shape",
            metric=FAIL_METRIC)
    if d["steady_misses"]:
        fail_structured(
            f"kill-a-shard drill: rebuilt group recompiled in steady "
            f"state: {d['steady_misses']} misses", metric=FAIL_METRIC)
    if d["duplicate_terminals"]:
        fail_structured(
            f"kill-a-shard drill: {d['duplicate_terminals']} duplicate "
            f"terminals — the exactly-once audit does not span the "
            f"degradation", metric=FAIL_METRIC)
    if d["mesh_reshards"] < 1:
        fail_structured(
            "kill-a-shard drill: no mesh_reshard record journaled for "
            "the cross-mesh replay", metric=FAIL_METRIC)
    return {
        "serving_degraded_rebuild_ms": d["rebuild_ms"],
        "serving_degraded_mp": d["model_parallel"],
        "serving_degraded_replayed": d["replayed"],
        "serving_degraded_lost": d["lost"],
    }


def serving_main():
    """Serving smoke bench: continuous-batching decode throughput + TTFT
    on the tiny GPT config (ISSUE 3).  Same one-JSON-line contract as the
    training bench, selected via ``--serving`` /
    ``PADDLE_TPU_BENCH_MODE=serving``.  ``vs_baseline`` is 1.0 — there is
    no external baseline for this metric yet; the absolute fields
    (``value``, ``ttft_ms``) are the tracked quantities.

    A shared-prefix workload variant (ISSUE 5) then runs through the
    same warm engine with prefix reuse, emitting
    ``serving_prefix_hit_rate``, ``serving_kv_blocks_in_use`` and
    ``ttft_ms_paged``; greedy outputs must equal the no-cache recompute.

    A fleet failover smoke (ISSUE 6) then serves a batch through a
    2-replica :class:`Fleet` while a replica-scoped fault plan kills
    replica 1 mid-decode: supervision ejects it, re-dispatches its
    requests to the survivor, and rebuilds it — emitting
    ``serving_fleet_tokens_per_sec`` (aggregate, measured across the
    chaos), ``serving_fleet_failover_recovery_ms`` (measured
    eject-to-rejoin wall time), and ``serving_fleet_redispatches``.
    Every request must reach a terminal state exactly once.

    Finally the overload trace-replay (ISSUE 8, :func:`_trace_replay`)
    replays a seeded Poisson trace of mixed priorities/lengths against
    a priority engine and a no-priority baseline, emitting p50/p99
    TTFT/ITL under load plus preemption and shed counters — and fails
    structured unless high-priority p99 TTFT beats the baseline with
    every request terminal exactly once and zero steady-state compile
    misses."""
    import time as _time

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fault_tolerance import ServingFaultPlan
    from paddle_tpu.models import gpt_tiny, GPTForCausalLM
    from paddle_tpu.serving import Engine, Fleet, SyncSanitizer

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    eng = Engine(model, num_slots=4, max_seq=64, min_bucket=8,
                 block_size=8)
    # sync-point sanitizer on the measured engine: counts every
    # framework-level d2h transfer per decode step — the host-sync
    # baseline ROADMAP item 2 (on-device sampling / Pallas decode
    # kernel) must drive to zero (docs/ANALYSIS.md)
    eng.sanitizer = SyncSanitizer()
    eng.warmup()
    rs = np.random.RandomState(0)
    lengths = [5, 13, 21, 34, 9, 17, 48, 3, 27, 11, 40, 6]
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in lengths]
    eng.generate(prompts, max_new_tokens=12)
    st = eng.stats()
    if st["compile_cache"]["misses"] != len(eng.buckets) + 1:
        fail_structured(
            f"steady-state recompile detected: {st['compile_cache']}",
            metric="serving_gpt_tiny_decode_tokens_per_sec")

    # -- shared-prefix workload, on the same warm engine ------------------
    shared = rs.randint(0, 128, (16,)).tolist()     # 2 blocks of 8
    tails = [rs.randint(0, 128, (t,)).tolist()
             for t in (5, 9, 3, 12, 7, 2, 10, 6)]
    sp_prompts = [shared + t for t in tails]
    # prime one pass so the measured pass is steady state with a
    # populated prefix cache
    eng.generate(sp_prompts, max_new_tokens=8)
    p_reqs = [eng.add_request(p, max_new_tokens=8) for p in sp_prompts]
    blocks_in_use_peak = 0
    while eng.step():
        blocks_in_use_peak = max(
            blocks_in_use_peak, eng._paging_snapshot()["blocks_in_use"])
    pst = eng.stats()
    if pst["compile_cache"]["misses"] != len(eng.buckets) + 1:
        fail_structured(
            f"paged steady-state recompile detected: "
            f"{pst['compile_cache']}",
            metric="serving_gpt_tiny_decode_tokens_per_sec")

    def _no_cache_greedy(prompt, out_ids):
        # ONE full-recompute forward of prompt + outputs: causal
        # attention makes its logits those of a token-by-token loop
        seq = np.asarray(prompt + out_ids[:-1], np.int64)[None]
        with paddle.no_grad():
            logits = model(paddle.to_tensor(seq)).numpy()[0]
        return logits[len(prompt) - 1:].argmax(-1).tolist()

    if any(r.output_ids != _no_cache_greedy(p, r.output_ids)
           for p, r in zip(sp_prompts, p_reqs)):
        fail_structured(
            "paged greedy outputs diverge from the no-cache recompute",
            metric="serving_gpt_tiny_decode_tokens_per_sec")
    if any(not r.finished for r in p_reqs) or \
            pst["health"]["kv_block_invariants"] != "ok":
        fail_structured(
            f"paged shared-prefix workload unhealthy: "
            f"{pst['health']}", metric="serving_gpt_tiny_decode_tokens_per_sec")

    # -- fleet failover smoke: kill 1 of 2 replicas mid-decode -----------
    plan = ServingFaultPlan().add("serving.r1.decode", at_call=2, times=2)
    fleet = Fleet(model, num_replicas=2, num_slots=2, max_seq=64,
                  min_bucket=8, block_size=8, eject_after_failures=2,
                  max_redispatch=2, fault_plan=plan)
    fleet.warmup()
    f_prompts = [rs.randint(0, 128, (L,)).tolist()
                 for L in (5, 11, 7, 16, 4, 9)]
    terminals = []
    t0 = _time.perf_counter()
    f_reqs = [fleet.submit(p, max_new_tokens=8,
                           # pin one stream onto the doomed replica so the
                           # fault is guaranteed to orphan in-flight work
                           replica=1 if i == 0 else None,
                           done_cb=lambda fr: terminals.append(fr.request_id))
              for i, p in enumerate(f_prompts)]
    fleet.run()
    fleet_dt = _time.perf_counter() - t0
    fst = fleet.stats()
    sup = fst["supervision"]
    if sorted(terminals) != sorted(r.request_id for r in f_reqs) or \
            fst["requests"]["duplicate_terminals"] != 0:
        fail_structured(
            f"fleet terminal contract violated: {fst['requests']}",
            metric="serving_gpt_tiny_decode_tokens_per_sec")
    if any(not r.finished for r in f_reqs):
        fail_structured(
            f"fleet chaos left unfinished requests: "
            f"{[(r.state, r.error) for r in f_reqs if not r.finished]}",
            metric="serving_gpt_tiny_decode_tokens_per_sec")
    if sup["ejections"] != 1 or sup["rebuilds"] != 1 or \
            fst["dispatch"]["redispatches"] < 1:
        fail_structured(
            f"fleet failover did not run as scripted: {sup}, "
            f"{fst['dispatch']}", metric="serving_gpt_tiny_decode_tokens_per_sec")
    fleet_tokens = sum(len(r.output_ids) for r in f_reqs)
    fleet.shutdown(timeout_s=0.0)

    # -- paged-kernel vs reference-gather decode microbench --------------
    kernel_bench = _paged_kernel_microbench(model)

    # -- speculative decoding: tiny-draft propose / bucketed verify ------
    spec_bench = _spec_decode_drill(model)

    # -- overload trace-replay: priorities vs the no-priority baseline ---
    trace = _trace_replay(model)

    # -- durability: crash recovery + rolling weight hot-swap ------------
    durability = _durability_drill(model)
    hot_swap = _hot_swap_drill(model)

    # -- tensor-parallel sharded serving: 2-shard vs single-chip ---------
    sharded = _sharded_serving_drill()

    # -- degraded-mode serving: SIGKILL a shard, rebuild smaller ---------
    degraded = _degraded_serving_drill()

    # -- multi-tenant: LoRA lanes + grammar masks on one paged engine ----
    tenancy = _multi_tenant_drill(model)

    def _p50_ttft_ms(reqs):
        ts = sorted(r.ttft_s for r in reqs)
        return round(ts[len(ts) // 2] * 1e3, 3)

    fl = st["failures"]
    print(json.dumps({
        "metric": "serving_gpt_tiny_decode_tokens_per_sec",
        "value": st["decode_tokens_per_sec"],
        "unit": "tokens/s",
        **_device_tags(),
        "vs_baseline": 1.0,
        "ttft_ms": st["ttft_ms"]["p50"],
        "ttft_p99_ms": st["ttft_ms"]["p99"],
        "inter_token_ms": st["inter_token_ms"]["p50"],
        "requests_completed": st["requests"]["completed"],
        "slot_occupancy": st["slot_occupancy"],
        "compile_misses": st["compile_cache"]["misses"],
        # resilience counters (ISSUE 4): all zero on the smoke path —
        # any nonzero value here flags a failure/retry during the bench
        "requests_failed": fl["failed"],
        "requests_cancelled": fl["cancelled"],
        "requests_rejected": fl["rejected"],
        "deadline_expired": fl["deadline_expired"],
        "step_retries": fl["step_retries"],
        "engine_state": st["health"]["state"],
        # per-decode-step device→host transfer count measured by the
        # sync-point sanitizer (ISSUE 7) — 0.0 since ISSUE 11 moved
        # sampling on-device (the PR 7 baseline was 1.0: the host-side
        # sampling logits pull; the decode dispatch now performs no
        # blocking host transfer, and the stream-delivery token pull
        # happens outside the sanitizer window by design)
        "serving_decode_host_transfers":
            st["sanitizer"]["per_decode_step"],
        # paged-kernel vs reference-gather decode microbench (ISSUE 11):
        # bitwise-equal greedy outputs enforced; the speedup ratio
        # tracks the Pallas flash-decoding path against the jnp gather
        # oracle (interpret-mode number off-TPU)
        **kernel_bench,
        # speculative decoding (ISSUE 15): greedy bitwise vs the
        # non-speculative run enforced, zero steady-state misses in
        # BOTH modes enforced; accept rate × tokens/round are the
        # efficiency trajectory, the tokens/sec pair the honest CPU
        # comparison (a random-weight draft prices the overhead; the
        # win needs a distilled draft + hardware)
        **spec_bench,
        # paged KV + prefix reuse (ISSUE 5): the shared-prefix workload
        # — hit rate must be > 0, and the TTFT reflects prefilling only
        # the uncached tail bucket
        "serving_prefix_hit_rate": pst["paging"]["prefix"]["hit_rate"],
        "serving_kv_blocks_in_use": blocks_in_use_peak,
        "serving_kv_blocks_total": pst["paging"]["blocks"]["total"],
        "ttft_ms_paged": _p50_ttft_ms(p_reqs),
        "paged_copy_on_extends": pst["paging"]["copy_on_extends"],
        "paged_engine_state": pst["health"]["state"],
        # fleet failover smoke (ISSUE 6): aggregate throughput measured
        # ACROSS the scripted replica kill (so it prices the failover
        # in), the measured eject-to-rejoin recovery, and how many
        # requests had to be replayed onto a survivor
        "serving_fleet_tokens_per_sec": round(fleet_tokens / fleet_dt, 2),
        "serving_fleet_failover_recovery_ms": sup["last_recovery_ms"],
        "serving_fleet_redispatches": fst["dispatch"]["redispatches"],
        "serving_fleet_affinity_hit_rate":
            fst["dispatch"]["affinity_hit_rate"],
        # overload trace-replay (ISSUE 8): p50/p99 TTFT and ITL under a
        # seeded Poisson overload of mixed priorities/lengths, the
        # preemption/shed counters, and the headline comparison — high-
        # priority p99 TTFT with priority scheduling vs the no-priority
        # baseline on the identical trace (enforced <)
        **trace,
        # durability drills (ISSUE 14): journaled crash recovery
        # (recover + replay-to-completion wall time, requests replayed;
        # fails structured on any lost request or steady-state compile)
        # and the rolling hot-swap under live traffic (worst observed
        # per-request inter-token gap across the roll; fails structured
        # on any failed request, new compile key, or stale prefix hit
        # across the version epoch)
        **durability,
        **hot_swap,
        # tensor-parallel sharded serving (ISSUE 18): bitwise greedy
        # parity with the single-chip engine at zero steady-state
        # recompiles enforced in a 2-shard subprocess drill; the
        # throughput ratio prices the per-layer TP all-reduces on the
        # emulated mesh (expect < 1 off-hardware)
        **sharded,
        # degraded-mode serving (ISSUE 19): a real SIGKILL takes a
        # shard host mid-decode; the group rebuilds at the largest
        # viable mp' on the survivors and replays the journal
        # cross-mesh — lost == 0, bitwise parity vs the uninterrupted
        # oracle and zero steady-state recompiles all enforced
        **degraded,
        # multi-tenant serving (ISSUE 20): heterogeneous Poisson mix of
        # base / two LoRA adapters / JSON-grammar tenants through ONE
        # paged engine — zero steady-state compile misses, zero
        # cross-tenant prefix hits, and grammar_valid_rate == 1.0 all
        # enforced structurally; per-class TTFT and the adapter
        # hot-swap latency are the tracked trajectory
        **tenancy,
    }))


def _train_rollback_drill():
    """Divergence-sentry rollback drill (ISSUE 12): a tiny compiled
    train loop under ``ResilientLoop`` with an injected transient NaN
    (``train.nan`` fault point).  The in-graph sentry must latch, roll
    back to the memory-snapshot ring, and skip the window — the drill
    fails structured otherwise — and emits the measured restore time as
    ``train_rollback_recovery_ms`` plus the sentry counters (pinned in
    tests/test_bench_smoke.py).  Runs the exact recovery path a 13B
    multi-chip job would take, at toy scale.

    The drill also carries the training step observatory (ISSUE 13): a
    ``StepTimeline`` records every attempt, the chain validator must
    pass with the injected rollback present as a ``rolled_back`` span
    in the Perfetto export (written to
    ``$PADDLE_TPU_TRACE_DIR/train_trace.json`` when set), emitted as
    ``train_step_trace_valid`` == 1.0."""
    import tempfile

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import obs
    from paddle_tpu.distributed.fault_tolerance import (
        DivergenceSentry, FaultPlan, ResilientLoop, global_grad_norm)

    paddle.seed(7)
    net = nn.Linear(8, 8)
    opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                 parameters=net.parameters())
    sentry = DivergenceSentry(window=8, min_history=2, spike_factor=8.0,
                              grad_ratio=100.0, snapshot_every=2,
                              ring_capacity=2, max_rollbacks=2)
    plan = FaultPlan().add_train_fault("train.nan", 5)

    @paddle.jit.to_static
    def train_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        sentry.observe(loss, grad_norm=global_grad_norm(net.parameters()))
        opt.step()
        opt.clear_grad()
        return loss

    def step_fn(step):
        rs = np.random.RandomState(100 + step)
        x = plan.corrupt_batch(step, rs.randn(4, 8).astype(np.float32))
        train_step(paddle.to_tensor(x))

    timeline = obs.StepTimeline()
    with tempfile.TemporaryDirectory(prefix="bench_sentry_") as ckdir:
        loop = ResilientLoop(
            ckdir,
            state_fn=lambda: {"model": net.state_dict(),
                              "opt": opt.state_dict()},
            restore_fn=lambda s: (net.set_state_dict(s["model"]),
                                  opt.set_state_dict(s["opt"])),
            save_every=None, save_final=False, sentry=sentry,
            verbose=False, timeline=timeline)
        loop.run(step_fn, 8)
    if sentry.rollbacks < 1 or sentry.anomalies < 1 \
            or loop.last_rollback_recovery_s is None:
        fail_structured(
            f"sentry rollback drill did not recover as scripted: "
            f"{loop.sentry_stats()}")
    final = np.asarray(net.state_dict()["weight"].numpy())
    if not np.isfinite(final).all():
        fail_structured("sentry rollback drill left non-finite weights")

    # -- step observatory (ISSUE 13): the drill's timeline must
    # chain-validate and the rollback must be visible in the export
    problems = obs.validate_timeline(timeline)
    if problems:
        fail_structured("train step timeline invalid: "
                        + "; ".join(problems[:5]))
    chrome = obs.chrome_trace(timeline)
    rolled = [e for e in chrome["traceEvents"]
              if e.get("ph") == "X"
              and e.get("args", {}).get("state") == "rolled_back"]
    if not rolled:
        fail_structured("injected sentry rollback missing from the "
                        "exported Perfetto trace")
    json.dumps(chrome)                  # Perfetto loads plain JSON
    trace_dir = os.environ.get("PADDLE_TPU_TRACE_DIR")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        obs.write_chrome_trace(
            timeline, os.path.join(trace_dir, "train_trace.json"))
    return {
        "train_rollback_recovery_ms": round(
            loop.last_rollback_recovery_s * 1e3, 3),
        "train_sentry_anomalies": sentry.anomalies,
        "train_sentry_rollbacks": sentry.rollbacks,
        "train_sentry_skipped_steps": sentry.skipped_steps,
        # chain validator passed (checked above — reaching here IS the
        # proof), rollback span present in the Perfetto export
        "train_step_trace_valid": 1.0,
        "train_step_trace_events": len(timeline.events),
    }


def _tp_overlap_drill_child():
    """Child half of the TP-overlap drill (``--tp-overlap-drill``):
    compile the tiny-GPT TP=4 train program twice — chunks=1 baseline
    and the chunked compute/collective-overlap schedule — on the
    8-device virtual CPU mesh, and print one JSON line with loss
    parity, the collective-exposure counts of both optimized HLOs, the
    overlapped schedule fingerprint (analyzed twice for stability), and
    the executable-cache delta."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fault_tolerance import global_grad_norm
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.obs import CostLedger

    s = paddle.distributed.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 4}
    fleet.init(is_collective=True, strategy=s)
    seq = 32
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randint(0, 128, (4, seq)))
    y = paddle.to_tensor(rs.randint(0, 128, (4, seq)))

    def build(chunks):
        paddle.seed(7)
        # the strategy path the user-facing config takes:
        # tensor_parallel_configs.overlap_chunks → distributed_model →
        # TensorParallel(tp_overlap=...) → apply_tp_overlap
        s.tensor_parallel_configs = {"overlap_chunks": chunks}
        model = fleet.distributed_model(GPTForCausalLM(gpt_tiny()))

        @paddle.jit.to_static
        def fwd_bwd(x, y):
            loss = model.compute_loss(x, y)
            loss.backward()
            g = global_grad_norm(model.parameters())
            model.clear_gradients()
            return loss, g

        return fwd_bwd

    base_fn, ovl_fn = build(1), build(4)
    l0, l1 = base_fn(x, y), ovl_fn(x, y)
    keys = set(ovl_fn.program_cache.keys())
    # CPU-mesh child: only counts and fingerprints are read from the
    # records; the roofline fields are a projection onto the named chip
    cost = CostLedger(chip="TPU v5 lite")
    rb = cost.add("base", base_fn, x, y)
    ro = cost.add("ovl", ovl_fn, x, y)
    ro2 = cost.add("ovl_again", ovl_fn, x, y)
    print(json.dumps({
        "loss_delta": abs(float(l0[0]) - float(l1[0])),
        "base_exposed": rb["collective_exposure"]["exposed"],
        "ovl_exposed": ro["collective_exposure"]["exposed"],
        "ovl_total": ro["collective_exposure"]["total"],
        "ovl_overlapped": ro["collective_exposure"]["overlapped"],
        "fingerprint": ro["fingerprint"],
        "fingerprint_stable":
            1.0 if ro["fingerprint"] == ro2["fingerprint"] else 0.0,
        "new_cache_keys": len(set(ovl_fn.program_cache.keys()) - keys),
    }))


def _tp_overlap_drill():
    """Compute/collective-overlap drill (ISSUE 16): run the TP=4
    chunked-schedule comparison in a subprocess pinned to the virtual
    CPU mesh (the parent may hold a real TPU backend), and fail the
    bench structured if the overlap schedule does not strictly REDUCE
    exposed collectives, breaks f32 loss parity, destabilizes the
    schedule fingerprint, or adds executable-cache keys."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        env["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    env.pop("PADDLE_TPU_BENCH_SMOKE", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--tp-overlap-drill"],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        fail_structured("tp-overlap drill crashed: "
                        + (proc.stderr or proc.stdout)[-800:])
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    if not lines:
        fail_structured(f"tp-overlap drill emitted no JSON: "
                        f"{proc.stdout[-400:]!r}")
    d = json.loads(lines[-1])
    if d["ovl_exposed"] >= d["base_exposed"]:
        fail_structured(
            f"TP overlap schedule did not reduce exposed collectives: "
            f"overlapped program {d['ovl_exposed']} vs chunks=1 "
            f"baseline {d['base_exposed']}")
    if d["loss_delta"] > 1e-4:
        fail_structured(f"TP overlap loss parity broken: {d}")
    if d["fingerprint_stable"] != 1.0:
        fail_structured(f"TP overlap schedule fingerprint unstable: {d}")
    if d["new_cache_keys"]:
        fail_structured(
            f"TP overlap analysis leaked executable-cache keys: {d}")
    return {
        "train_tp_overlap_enabled": 1.0,
        "train_tp_overlap_exposed_collectives": d["ovl_exposed"],
        "train_tp_overlap_fingerprint": d["fingerprint"],
    }


def _elastic_drill_child():
    """Child half of the elastic drill (``--elastic-drill-child``): on
    the 8-device virtual CPU mesh, train at dp=4, abandon the run past
    its last committed generation, relaunch the rig at dp=2 over half
    the devices, and resume through ``ResilientLoop`` — proving the
    resharded state bitwise identical to the generation's global arrays,
    replaying exactly the uncommitted steps, losing zero samples of the
    elastic data schedule, and adding zero steady-state compiles after
    the post-resume rebuild.  Prints one JSON line."""
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import checkpoint as ckpt, mesh as mesh_mod
    from paddle_tpu.distributed.fault_tolerance import ResilientLoop
    from paddle_tpu.distributed.reshard import (
        ElasticDataSchedule, verify_resharded)
    from paddle_tpu.distributed.sharding_spec import shard_parameter
    from paddle_tpu.obs import CompileLedger

    G, STEPS, CUT = 8, 8, 5    # global batch; total steps; interrupt point

    def rig(dp, mp, devices=None):
        mesh = mesh_mod.hybrid_mesh(dp=dp, mp=mp, devices=devices)
        mesh_mod.set_global_mesh(mesh)
        paddle.seed(11)
        net = nn.Linear(8, 4, weight_attr=paddle.ParamAttr(name="el_w"),
                        bias_attr=paddle.ParamAttr(name="el_b"))
        shard_parameter(net.weight, P(None, "model"), mesh)
        opt = paddle.optimizer.AdamW(learning_rate=0.05,
                                     parameters=net.parameters())
        sched = ElasticDataSchedule(G)
        losses = []

        def step_fn(step):
            # batch derived from the schedule's step window: the sample
            # stream is a pure function of the step, world-independent
            lo, _hi = sched.step_window(step)
            rs = np.random.RandomState(lo)
            x = paddle.to_tensor(rs.randn(G, 8).astype(np.float32))
            loss = (net(x) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))

        return {
            "net": net, "opt": opt, "step_fn": step_fn, "losses": losses,
            "sched": sched,
            "state_fn": lambda: {"model": net.state_dict(),
                                 "opt": opt.state_dict()},
            "restore_fn": lambda s: (net.set_state_dict(s["model"]),
                                     opt.set_state_dict(s["opt"])),
        }

    with tempfile.TemporaryDirectory() as tmp:
        # oracle: uninterrupted dp=4 run
        r0 = rig(4, 2)
        ResilientLoop(os.path.join(tmp, "ref"), r0["state_fn"],
                      r0["restore_fn"], save_every=None,
                      verbose=False).run(r0["step_fn"], STEPS)
        mesh_mod.set_global_mesh(None)

        # life 1 at dp=4: cadence saves, no final commit (the "kill")
        root = os.path.join(tmp, "ck")
        r1 = rig(4, 2)
        ResilientLoop(root, r1["state_fn"], r1["restore_fn"],
                      save_every=2, save_final=False,
                      verbose=False).run(r1["step_fn"], CUT)
        gen, path = ckpt.latest_valid(root)
        ref_gen = ckpt.load_state_dict(path, return_numpy=True)
        mesh_mod.set_global_mesh(None)

        # life 2 at dp=2 over HALF the devices
        r2 = rig(2, 2, devices=jax.devices()[:4])
        t0 = time.perf_counter()
        probe = ResilientLoop(root, r2["state_fn"], r2["restore_fn"],
                              verbose=False)
        resumed = probe.resume()
        reconfig_ms = (time.perf_counter() - t0) * 1e3
        digest_ok = 1.0
        try:
            verify_resharded({"model": r2["net"].state_dict(),
                              "opt": r2["opt"].state_dict()},
                             ref_gen["user"])
        except ValueError as e:
            digest_ok = 0.0
            print(str(e)[:800], file=sys.stderr)
        ledger = CompileLedger(name="elastic")
        loop2 = ResilientLoop(root, r2["state_fn"], r2["restore_fn"],
                              save_every=2, verbose=False,
                              compile_ledger=ledger)
        loop2.run(r2["step_fn"], STEPS)
        lost = r2["sched"].lost_samples([(0, gen, 4), (gen, STEPS, 2)])
        tail = r0["losses"][resumed:]
        delta = max(abs(a - b) for a, b in zip(r2["losses"], tail)) \
            if r2["losses"] and len(r2["losses"]) == len(tail) else -1.0
    print(json.dumps({
        "resumed_gen": resumed,
        "replayed_steps": CUT - resumed,
        "reconfig_ms": round(reconfig_ms, 3),
        "loop_reconfigs": probe.reconfigs + loop2.reconfigs,
        "resharded_tensors": len(loop2.reshard_report),
        "digest_ok": digest_ok,
        "lost_samples": lost,
        "steady_misses": ledger.steady_state_misses,
        "loss_tail_delta": delta,
    }))


def _elastic_drill():
    """Elastic reconfiguration drill (ISSUE 17): run the dp=4 → dp=2
    resume in a subprocess pinned to the virtual CPU mesh, and fail the
    bench structured if the resharded state is not bitwise identical to
    the committed generation, if any sample of the elastic data
    schedule is lost or duplicated across the world change, or if the
    post-resume steady state recompiled."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        env["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    env.pop("PADDLE_TPU_BENCH_SMOKE", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--elastic-drill-child"],
        capture_output=True, text=True, env=env, timeout=600)
    if proc.returncode != 0:
        fail_structured("elastic drill crashed: "
                        + (proc.stderr or proc.stdout)[-800:])
    lines = [ln for ln in proc.stdout.strip().splitlines()
             if ln.startswith("{")]
    if not lines:
        fail_structured(f"elastic drill emitted no JSON: "
                        f"{proc.stdout[-400:]!r}")
    d = json.loads(lines[-1])
    if d["digest_ok"] != 1.0:
        fail_structured(
            "elastic resume resharded state is NOT bitwise identical to "
            f"the committed generation: {d}")
    if d["lost_samples"] != 0:
        fail_structured(
            f"elastic reconfiguration lost/duplicated samples: {d}")
    if d["steady_misses"]:
        fail_structured(
            f"post-resume steady state recompiled: {d}")
    if d["loop_reconfigs"] < 2:       # probe resume + loop2 resume
        fail_structured(
            f"topology change was not detected as a reconfig: {d}")
    if not 0 <= d["loss_tail_delta"] <= 1e-4:
        fail_structured(
            f"elastic resume broke loss parity with the uninterrupted "
            f"run: {d}")
    return {
        "train_elastic_reconfig_ms": d["reconfig_ms"],
        "train_elastic_replayed_steps": d["replayed_steps"],
        "train_elastic_lost_samples": d["lost_samples"],
    }


def main():
    import jax
    from paddle_tpu.core.chip import attached_chip
    from paddle_tpu.obs import CompileLedger, CostLedger

    smoke = bool(os.environ.get("PADDLE_TPU_BENCH_SMOKE"))
    if smoke:
        # CPU correctness smoke of the bench path: no device rate exists
        # here, so no MFU is formed; the analytic roofline is projected
        # onto a chip named here (and in the row), never onto a default
        peaks, cost_chip = None, "TPU v5 lite"
    else:
        # the measurement: a TPU from the peaks table or an error
        chip, peaks = attached_chip()
        cost_chip = chip["kind"]
    make_step, cfg, seq, model = build_bench(smoke=smoke)
    per_chip = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", "8"))
    batch = per_chip * len(jax.devices())

    # compile ledger (ISSUE 13): every executable-cache miss of the
    # measured run is recorded — cumulative compile wall time becomes a
    # reported metric, and a compile AFTER warmup (a steady-state miss)
    # fails the bench as the named anomaly it is
    ledger = CompileLedger(name="bench")
    ledger.attach()
    train_step, x, y = make_step(batch)
    for _ in range(3):          # warmup (compile)
        loss = train_step(x, y)
    float(loss)
    ledger.mark_steady()        # timed loop must add ZERO compiles
    n_iters = 10
    t0 = time.perf_counter()
    for _ in range(n_iters):
        loss = train_step(x, y)
    float(loss)  # sync
    dt = (time.perf_counter() - t0) / n_iters
    ledger.detach()
    if ledger.steady_state_misses:
        fail_structured(
            f"training steady state recompiled: {ledger.anomalies()}")

    n_chips = max(len(jax.devices()), 1)
    tokens_per_sec = batch * seq / dt / n_chips  # per-chip, honest on pods
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # 6ND for fwd+bwd (+ attention term ~ 12*L*h*s^2 folded via 6N upper
    # bound convention used by the scaling literature)
    flops_per_token = 6.0 * n_params
    mfu = (tokens_per_sec * flops_per_token / peaks.bf16_flops_per_s
           if peaks else None)

    # cost/fingerprint ledger (ISSUE 13): XLA's own cost analysis of
    # the EXACT program just timed — analytic roofline MFU, arithmetic
    # intensity, and the schedule fingerprint.  The smoke path analyzes
    # TWICE to prove the fingerprint is stable for identical programs
    # (the regression surface the compute/collective-overlap work will
    # move on purpose); the hardware path skips the re-analysis — each
    # analyze is a full XLA lower+compile, seconds at 345M, and
    # stability is already pinned every CI run in test_train_obs
    cost = CostLedger(chip=cost_chip)
    rec = cost.add("train_step", train_step, x, y,
                   tokens_per_step=batch * seq, n_params=n_params)
    if smoke:
        rec2 = cost.add("train_step", train_step, x, y,
                        tokens_per_step=batch * seq, n_params=n_params)
        if rec["fingerprint"] != rec2["fingerprint"]:
            fail_structured(
                f"schedule fingerprint unstable across identical "
                f"analyses: {rec['fingerprint']} != {rec2['fingerprint']}")

    # divergence-sentry recovery drill (ISSUE 12, step observatory
    # ISSUE 13): enforced to actually roll back with a chain-valid
    # step timeline, priced separately from the throughput measurement
    rollback = _train_rollback_drill()
    # compute/collective-overlap drill (ISSUE 16): prove on the virtual
    # mesh that the chunked TP schedule strictly reduces exposed
    # collectives at f32 loss parity, and report its exposure metrics
    overlap = _tp_overlap_drill()
    # elastic reconfiguration drill (ISSUE 17): prove a dp=4 → dp=2
    # resume reshards bitwise-identically, replays only uncommitted
    # steps, and loses zero samples of the elastic data schedule
    elastic = _elastic_drill()
    out = {
        "metric": "gpt2_345m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        **_device_tags(),
        "batch_per_chip": per_chip,
        # measured rate over the attached chip's published bf16 peak;
        # None on the CPU smoke, where there is no device rate
        "vs_baseline": round(mfu / 0.45, 4) if mfu is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "step_ms": round(dt * 1000, 2),
        "loss": float(loss),
        # compile ledger (ISSUE 13): how many XLA compiles the run paid
        # and their cumulative wall seconds; the steady-state window
        # added zero (enforced above — the run fails otherwise)
        "train_compile_count": ledger.compiles,
        "train_compile_seconds": round(ledger.total_seconds, 3),
        # cost ledger (ISSUE 13): hardware-independent program facts
        "train_analytic_mfu": rec["analytic_mfu"],
        "train_arith_intensity": rec["arithmetic_intensity"],
        "train_flops_vs_6nd": rec["flops_vs_6nd"],
        "train_schedule_fingerprint": rec["fingerprint"],
        "train_cost_chip": cost.chip,
        **rollback,
        **overlap,
        **elastic,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    # the hidden child modes run on the virtual CPU mesh their parent
    # pinned via env (JAX_PLATFORMS=cpu): they never ask for the chip,
    # which belongs to the one parent process
    if "--tp-overlap-drill" in sys.argv:
        _tp_overlap_drill_child()
        sys.exit(0)
    if "--elastic-drill-child" in sys.argv:
        # dp=4 → dp=2 reconfigured resume
        _elastic_drill_child()
        sys.exit(0)
    if "--sharded-serving-drill" in sys.argv:
        # model=2 TP engine vs single-chip
        _sharded_serving_drill_child()
        sys.exit(0)
    if "--degraded-serving-serve-child" in sys.argv:
        # kill-a-shard drill, serve half: journaled streaming traffic
        # on a model=2 mesh, SIGKILLs itself mid-decode
        _degraded_serving_serve_child()
        sys.exit(0)
    if "--degraded-serving-recover-child" in sys.argv:
        # kill-a-shard drill, recovery half: degraded rebuild on the
        # survivor + cross-mesh journal replay, one JSON line
        _degraded_serving_recover_child()
        sys.exit(0)
    import jax

    if os.environ.get("PADDLE_TPU_BENCH_SMOKE"):
        # CPU smoke mode exercises the exact bench path on tiny shapes
        # (tests/test_bench_smoke); it must never take the chip
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() == "tpu":
        # compiled programs persist across the processes of one command;
        # CPU runs stay cache-less like the test suite (tests/conftest.py)
        from paddle_tpu.core.chip import place_compile_cache

        place_compile_cache()
    _serving = "--serving" in sys.argv or \
        os.environ.get("PADDLE_TPU_BENCH_MODE") == "serving"
    try:
        # main() checks the platform in-process (core.chip.attached_chip):
        # anything but a TPU from the peaks table is a structured failure
        serving_main() if _serving else main()
    except Exception as e:  # noqa: BLE001 — structured failure contract
        import traceback

        traceback.print_exc(file=sys.stderr)
        fail_structured(
            f"{type(e).__name__}: {e}",
            metric="serving_gpt_tiny_decode_tokens_per_sec" if _serving
            else "gpt2_345m_train_tokens_per_sec_per_chip")

"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a TPU, from the root of a checkout

One process, which holds the chip.  It drives the two main paths once,
through the entry points a user calls, at the full width and depth of
GPT-2 345M (hidden 1024, 24 layers, 16 heads x 64, vocab 50304) with
seeded random weights:

- **train**: the step ``bench.py`` measures (``bench.build_train_step``:
  fleet.init -> distributed_model -> AdamW -> AMP-O2 -> ``@to_static`` step
  with bf16 autocast and the streamed fused CE), batch 8 x 1024 per chip,
  a few steps on one repeated batch;
- **serve**: ``inference.create_engine("gpt:gpt2-345m", ...)`` with the
  default ``kernel="pallas"`` -> ``warmup()`` -> requests of
  mixed prompt lengths, greedy and sampled, two sharing a prefix ->
  ``run()``; then the same greedy requests through a second engine over
  the same model with ``kernel="reference"`` (the jnp gather oracle);
- **flash**: the Pallas flash kernels (forward and both backward) against
  ``_sdpa_reference`` at the training shape.

With four or more devices it also trains the same step under
``hybrid_configs`` dp x mp=2 and serves through a ``serving_mesh`` over
every device (chosen from the device count; there is no flag).

Every check is listed in the output; any failed check or exception makes
the exit code 1.  There is no CPU mode: ``__main__`` exits 2 before doing
anything else unless ``jax.devices()[0]`` is a TPU whose ``device_kind``
is in the repo's peaks table.  Timings are printed as information, not
claims.  The phases are plain functions of a model config so that
tests/test_chip_smoke.py runs the same code with ``gpt_tiny`` on the CPU
(interpret-mode kernels).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
the line before it (``SMOKE_SUMMARY {...}``) carries losses, tokens and
timings for comparing two runs.
"""
from __future__ import annotations

import gc
import json
import math
import re
import sys
import time
import traceback

import numpy as np

#: ``custom_call_target`` of a Mosaic (Pallas TPU) kernel in optimized HLO
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


def say(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail records; every one is printed as it is made."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: list = []

    def check(self, name: str, ok, detail="") -> bool:
        ok = bool(ok)
        say(f"  [{'ok' if ok else 'FAIL'}] {self.phase}: {name}"
            + (f" — {detail}" if detail != "" else ""))
        if not ok:
            self.failed.append(f"{self.phase}: {name} — {detail}")
        return ok


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _peak_bytes() -> list:
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


# -- train -------------------------------------------------------------------

def train_phase(cfg, *, seq: int, batch_per_device: int, steps: int,
                hybrid_configs: dict = None) -> dict:
    """Build ``bench.build_train_step(cfg, seq, hybrid_configs)`` and take
    ``steps`` steps on one repeated batch, each ended by ``float(loss)``.

    Checks: losses finite, first within ln(vocab) +- 0.7, last lower than
    first; zero compiles after the first step; the optimized HLO of the
    step took the attention path this backend must take (on a TPU the
    Pallas flash kernels, ``layers x (1 fwd + 2 bwd)`` Mosaic custom calls
    and no XLA-oracle attention; elsewhere the oracle).  Under
    ``hybrid_configs`` also: the HLO has collectives and a parameter's
    sharding spans every device."""
    import jax
    from bench import build_train_step
    from paddle_tpu.obs import CompileLedger
    from paddle_tpu.obs.hlo_cost import collective_exposure
    from paddle_tpu.ops.pallas import ATTN_SCOPE_PALLAS, ATTN_SCOPE_XLA

    name = "train" if not hybrid_configs else "train-hybrid"
    c = Checks(name)
    n_dev = len(jax.devices())
    batch = batch_per_device * n_dev
    ledger = CompileLedger(name=f"chip_smoke.{name}").attach()
    try:
        make_step, model = build_train_step(cfg, seq, hybrid_configs)
        train_step, x, y = make_step(batch)
        t0 = time.perf_counter()
        losses = [float(train_step(x, y))]       # compiles
        compile_s = time.perf_counter() - t0
        ledger.mark_steady()
        step_s = []
        for _ in range(steps - 1):
            t0 = time.perf_counter()
            losses.append(float(train_step(x, y)))
            step_s.append(time.perf_counter() - t0)
    finally:
        ledger.detach()

    ln_v = math.log(cfg.vocab_size)
    c.check("losses finite", all(math.isfinite(v) for v in losses), losses)
    c.check("first loss within ln(vocab) +- 0.7",
            abs(losses[0] - ln_v) <= 0.7,
            f"{losses[0]:.4f} vs ln({cfg.vocab_size}) = {ln_v:.4f}")
    c.check("loss fell", losses[-1] < losses[0],
            f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    c.check("no compile after the first step",
            ledger.steady_state_misses == 0, ledger.anomalies())

    # the step's optimized HLO names the attention path it took
    hlo = train_step.get_concrete_program(x, y).compiled_stats()["hlo"]
    n_pallas = hlo.count(PALLAS_CALL)
    saw_pallas, saw_xla = ATTN_SCOPE_PALLAS in hlo, ATTN_SCOPE_XLA in hlo
    path = {(True, False): "pallas_flash", (False, True): "xla_sdpa"}.get(
        (saw_pallas, saw_xla), f"mixed(pallas={saw_pallas}, xla={saw_xla})")
    want_path = "pallas_flash" if _on_tpu() else "xla_sdpa"
    want_calls = 3 * cfg.num_hidden_layers if _on_tpu() else 0
    c.check(f"attention path is {want_path}", path == want_path, path)
    c.check(f"{want_calls} Pallas custom calls in the step's HLO "
            f"({cfg.num_hidden_layers} layers x (1 fwd + 2 bwd))",
            n_pallas == want_calls, n_pallas)
    peaks = _peak_bytes()
    out = {"phase": name, "batch": batch, "seq": seq, "losses": losses,
           "attention_path": path, "pallas_custom_calls": n_pallas,
           "compile_s": round(compile_s, 2),
           "step_s": [round(s, 4) for s in step_s],
           "compiles": ledger.compiles,
           "peak_bytes_in_use": peaks}
    if hybrid_configs:
        n_coll = collective_exposure(hlo)["total"]
        c.check("the step's HLO has collectives", n_coll > 0, n_coll)
        span = max(len(p._value().sharding.device_set)
                   for p in model.parameters())
        c.check(f"parameter shardings span {n_dev} devices", span == n_dev,
                span)
        if _on_tpu():                    # the CPU reports no memory stats
            c.check("every device's peak memory non-zero and within 2x "
                    "of the others",
                    min(peaks) > 0 and max(peaks) <= 2 * min(peaks), peaks)
        out.update(hybrid_configs=hybrid_configs, collectives=n_coll)
    out["failed"] = c.failed
    say(f"  {name}: attention path {path}; compile+first step "
        f"{compile_s:.1f}s; steady steps "
        f"{[round(s, 3) for s in step_s]} s; losses "
        f"{[round(v, 4) for v in losses]}")
    return out


# -- serve -------------------------------------------------------------------

def _decode_stats(eng) -> dict:
    """``compiled_stats()`` (optimized HLO, memory analysis) of the engine's
    one decode program, lowered under the contexts ``Engine._call_counted``
    runs it in."""
    from contextlib import nullcontext

    from paddle_tpu.core.autograd import no_grad

    (prog,) = eng._decode_fn.program_cache.values()
    mesh_ctx = eng.shard.context() if eng.shard is not None else nullcontext()
    with mesh_ctx, no_grad():
        return prog.compiled_stats()


_HLO_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                 "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                 "u64": 8}
_HLO_MOVE = re.compile(
    r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = (\w+)\[([\d,]*)\]\S*\s+"
    r"(copy|transpose|slice)\(", re.M)


def pool_sized_moves(hlo: str, nbytes: int) -> list:
    """The ``copy``, ``transpose`` and ``slice`` instructions of an optimized
    HLO module, fused computations included, whose result holds ``nbytes``
    or more (by its shape; a tiled layout's padding is not counted): what a
    program does to a KV pool that it cannot write and read where it is."""
    out = []
    for name, dtype, dims, op in _HLO_MOVE.findall(hlo):
        size = _HLO_ITEMSIZE.get(dtype, 0) * math.prod(
            int(d) for d in dims.split(",") if d)
        if size >= nbytes:
            out.append(f"{op} {name} {dtype}[{dims}]")
    return out


def _prompts(rs, vocab: int, lengths, shared_len: int) -> list:
    """One prompt per length, then two more that share a ``shared_len``
    prefix (different tails) — the pair the prefix cache must hit on."""
    prompts = [rs.randint(0, vocab, (int(n),)).tolist() for n in lengths]
    shared = rs.randint(0, vocab, (shared_len,)).tolist()
    for tail in (7, 12):
        prompts.append(shared + rs.randint(0, vocab, (tail,)).tolist())
    return prompts


def latent_model():
    """JoyAI-LLM-Flash's widths (latent attention 1536/512, 32 heads of
    128 + 64 / 128, experts of 768 top-8 of 256 with 32 held and a shared
    one) at a depth of one dense and two expert layers and a vocabulary cut
    to 8,192 for the smoke's time, created in bf16."""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)

    paddle.seed(0)
    return DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=8192, num_hidden_layers=3, held_experts=(0, 32),
        max_position_embeddings=2048, dtype="bfloat16"))


def serve_phase(model, *, max_seq: int, num_slots: int, block_size: int,
                min_bucket: int, prompt_lens, shared_len: int,
                max_new_tokens: int, model_parallel: int = None,
                expect_tokens: list = None, name: str = None,
                pallas_calls: int = None, exact: bool = True) -> dict:
    """Serve through ``inference.create_engine(model, ...)`` with the
    default ``kernel="pallas"``: ``warmup()``, one request
    per length in ``prompt_lens`` plus two sharing a ``shared_len`` prefix
    (greedy, except the last two of ``prompt_lens`` which sample), then
    ``run()``.

    ``model`` is anything ``create_engine`` accepts (``"gpt:gpt2-345m"``,
    a config, a Layer).  Checks: every request finished, nothing failed or
    retried (the first failure's error string is printed), compile misses
    == buckets + 1, prefix hit rate > 0, block invariants ok, the paged
    kernels compiled for the device (interpret mode only off-TPU) and are
    in the decode program's HLO, which on the chip moves no layer buffer of
    the KV pool (no ``copy``, ``transpose`` or ``slice`` of that size,
    both pools aliased; the one-chip engine's program only), the
    ``stats()["sampler"]`` counts (a greedy-only run after the mixed one
    counts greedy steps only).  Greedy
    outputs must equal those of a second engine over the same model with
    ``kernel="reference"``.
    ``model_parallel`` serves through ``serving_mesh(model_parallel)``,
    checks that the KV pool is sharded over that many devices, and
    compares greedy outputs with ``expect_tokens`` (the one-chip run's):
    every first token, and 3/4 of all tokens before each first flip.
    ``pallas_calls`` is the number of Pallas custom calls the decode program
    has to hold (default: one a layer); ``exact=False`` holds a one-chip
    engine to the sharded engine's agreement instead of to equality (bf16
    products on the MXU inside the kernel against XLA's in the oracle)."""
    import paddle_tpu as paddle
    from paddle_tpu import inference
    from paddle_tpu.serving import SamplingParams

    name = name or ("serve" if not model_parallel else "serve-sharded")
    c = Checks(name)
    kw = dict(block_size=block_size, min_bucket=min_bucket, max_seq=max_seq,
              num_slots=num_slots)
    if model_parallel:
        from paddle_tpu.serving.sharding import serving_mesh

        kw["mesh"] = serving_mesh(model_parallel)
    paddle.seed(0)
    eng = inference.create_engine(model, **kw)
    vocab = eng.config.vocab_size
    prompts = _prompts(np.random.RandomState(7), vocab, prompt_lens,
                       shared_len)
    n_sampled = 2
    greedy_idx = [i for i in range(len(prompts))
                  if not (len(prompt_lens) - n_sampled <= i
                          < len(prompt_lens))]

    def drive(engine):
        t0 = time.perf_counter()
        engine.warmup()
        warm_s = time.perf_counter() - t0
        reqs = []
        for i, p in enumerate(prompts):
            sampling = None if i in greedy_idx else SamplingParams(
                temperature=0.8, top_k=50, top_p=0.9, seed=1234 + i)
            reqs.append(engine.add_request(
                p, max_new_tokens=max_new_tokens, sampling=sampling))
        t0 = time.perf_counter()
        engine.run()
        return reqs, warm_s, time.perf_counter() - t0

    reqs, warm_s, run_s = drive(eng)
    st = eng.stats()
    unfinished = [(r.request_id, r.state, r.error) for r in reqs
                  if not r.finished]
    first_error = next((r.error for r in reqs if r.error), None)
    c.check("every request finished", not unfinished,
            f"{unfinished}; first error: {first_error}" if unfinished
            else len(reqs))
    c.check("no failed request", st["failures"]["failed"] == 0,
            f"{st['failures']['failed']}; first error: {first_error}")
    c.check("no step retry", st["failures"]["step_retries"] == 0,
            st["failures"]["step_retries"])
    c.check("compile misses == buckets + 1",
            st["compile_cache"]["misses"] == len(eng.buckets) + 1,
            f"{st['compile_cache']} for buckets {eng.buckets}")
    hit_rate = st["paging"]["prefix"]["hit_rate"]
    c.check("prefix hit rate > 0", hit_rate > 0, hit_rate)
    c.check("kv block invariants ok",
            st["health"]["kv_block_invariants"] == "ok",
            st["health"]["kv_block_invariants"])
    buckets_used = sorted({r.prefill_bucket for r in reqs})
    c.check("small, middle and largest prefill buckets all served",
            len(buckets_used) >= 3 and eng.buckets[0] in buckets_used
            and eng.buckets[-1] in buckets_used, buckets_used)
    vocab_ok = all(0 <= t < vocab for r in reqs for t in r.output_ids)
    c.check("every token in the vocabulary", vocab_ok)
    kernel = st["paging"]["kernel"]
    interpret = eng.cache._interpret
    path = f"paged {kernel}" + (" (interpret)" if interpret else "")
    c.check("the default engine runs the Pallas paged kernels",
            kernel == "pallas", kernel)
    c.check("paged kernels compiled for the device iff it is a TPU",
            interpret is (not _on_tpu()), f"_interpret={interpret}")
    decode = _decode_stats(eng)
    n_pallas = decode["hlo"].count(PALLAS_CALL)
    want = (pallas_calls or eng.config.num_hidden_layers) if _on_tpu() else 0
    c.check(f"{want} Pallas custom calls in the decode program's HLO",
            n_pallas == want, n_pallas)
    # the pool (K and V per head, or one latent vector a token) is written
    # and read where it is stored: one buffer per layer and side, donated,
    # in the kernels' own form (interpret mode's
    # emulation copies its operands, so only the chip's program is held to
    # it; a sharded engine's program is per shard and is not checked here)
    pools, layer_buf = eng.cache.nbytes(), eng.cache.layer_nbytes()
    say(f"  decode program: alias_bytes {decode.get('alias_bytes')} "
        f"temp_bytes {decode.get('temp_bytes')} pools {pools} "
        f"layer buffer {layer_buf}")
    if _on_tpu() and not model_parallel:
        moves = pool_sized_moves(decode["hlo"], layer_buf)
        c.check("no copy, transpose or slice of a layer buffer's size in "
                "the decode program", not moves, moves[:4] or layer_buf)
        c.check("the decode program aliases the whole pool and holds under one "
                "layer buffer of temporaries",
                decode["alias_bytes"] >= pools
                and decode["temp_bytes"] < layer_buf,
                (decode["alias_bytes"], decode["temp_bytes"]))
    # the sampler's way through each decode step, as the host counts it: a
    # step with no sampled request computes no cut-off and draws nothing
    smp = st["sampler"]
    say(f"  sampler steps: {smp}")
    c.check("the sampled requests' steps are counted",
            smp["steps_sampled"] > 0, smp)
    for p in prompts[:2]:
        eng.add_request(p, max_new_tokens=4)
    eng.run()
    after = eng.stats()["sampler"]
    say(f"  sampler steps after a greedy-only run: {after}")
    c.check("a greedy-only run counts greedy steps only",
            after["steps_greedy"] > smp["steps_greedy"]
            and after["steps_sampled"] == smp["steps_sampled"], after)
    tokens = [list(map(int, r.output_ids)) for r in reqs]
    greedy = [tokens[i] for i in greedy_idx]
    out = {"phase": name, "attention_path": path,
           "buckets": list(eng.buckets), "buckets_used": buckets_used,
           "prompt_lens": [len(p) for p in prompts],
           "prefix_hit_rate": hit_rate, "tokens": tokens,
           "warmup_s": round(warm_s, 2), "run_s": round(run_s, 3),
           "compile_misses": st["compile_cache"]["misses"]}
    if model_parallel:
        span = len(eng.cache.buffers()[0]._value().sharding.device_set)
        c.check(f"KV pool sharded over {model_parallel} devices",
                span == model_parallel, span)
        out["model_parallel"] = model_parallel
    ref_label = "the one-chip engine"
    if expect_tokens is None:
        # the oracle: same model, same requests, jnp gather attention
        ref_label = 'the kernel="reference" engine'
        ref = inference.create_engine(eng.model, kernel="reference", **kw)
        ref_reqs, ref_warm_s, ref_run_s = drive(ref)
        expect_tokens = [list(map(int, ref_reqs[i].output_ids))
                         for i in greedy_idx]
        ref_st = ref.stats()
        c.check("reference engine finished everything, nothing failed",
                all(r.finished for r in ref_reqs)
                and ref_st["failures"]["failed"] == 0
                and ref_st["paging"]["kernel"] == "reference",
                ref_st["failures"])
        out.update(reference_warmup_s=round(ref_warm_s, 2),
                   reference_run_s=round(ref_run_s, 3))
        del ref, ref_reqs
    diverged = [i for i, (a, b) in enumerate(zip(greedy, expect_tokens))
                if a != b]
    detail = (f"greedy requests {diverged} differ: "
              f"{[(greedy[i], expect_tokens[i]) for i in diverged[:2]]}"
              if diverged else f"{len(greedy)} requests")
    if not model_parallel and exact:
        c.check(f"greedy tokens equal {ref_label}", not diverged, detail)
    else:
        # TP changes the order of every row-parallel reduction, so a
        # near-tie argmax of a random-weight model may flip mid-decode
        # (1 request of 5 did, at its fifth token, on four chips at depth
        # 2); a sharding bug is wrong from the first token on.  Demand
        # the first token of every request and most of the rest.
        same = [next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     len(a)) for a, b in zip(greedy, expect_tokens)]
        total = sum(len(a) for a in greedy)
        c.check(f"first greedy token of every request equals {ref_label}",
                all(n >= 1 for n in same), same)
        c.check(f"greedy tokens agree with {ref_label} up to each "
                f"request's first flip on >= 3/4 of all tokens",
                4 * sum(same) >= 3 * total,
                f"{sum(same)} of {total}; {detail}")
        out["greedy_agreement"] = [sum(same), total]
    out["greedy_tokens"] = greedy
    out["failed"] = c.failed
    say(f"  {name}: attention path {path}; warmup (compile) {warm_s:.1f}s; "
        f"{len(reqs)} requests x {max_new_tokens} tokens in {run_s:.2f}s")
    return out


# -- flash kernels against the oracle ----------------------------------------

def flash_phase(shape) -> dict:
    """Pallas flash attention (forward and both backward kernels, causal,
    bf16) against ``_sdpa_reference`` in f32 at ``shape`` = [B, S, H, D],
    to bf16 tolerance.  Off-TPU the kernels run in interpret mode."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import _sdpa_reference
    from paddle_tpu.ops.pallas.flash_attention_kernel import \
        flash_attention_fused

    c = Checks("flash")
    interpret = not _on_tpu()
    rs = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rs.randn(*shape), jnp.bfloat16)
                  for _ in range(4))

    def with_grads(attn, cast):
        def run(q, k, v, g):
            o, vjp = jax.vjp(attn, cast(q), cast(k), cast(v))
            return (o,) + vjp(cast(g))
        return jax.jit(run)

    kernel = with_grads(
        lambda a, b, d: flash_attention_fused(a, b, d, causal=True,
                                              interpret=interpret),
        lambda t: t)
    oracle = with_grads(
        lambda a, b, d: _sdpa_reference(a, b, d, None, None, 0.0, True),
        lambda t: t.astype(jnp.float32))
    t0 = time.perf_counter()
    got = jax.block_until_ready(kernel(q, k, v, g))
    compile_s = time.perf_counter() - t0
    want = oracle(q, k, v, g)
    errs = {}
    for tag, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        tol = 2e-2 * max(1.0, float(np.abs(b).max()))
        errs[tag] = float(np.abs(a - b).max())
        c.check(f"{tag} within bf16 tolerance of _sdpa_reference",
                np.isfinite(a).all() and errs[tag] <= tol,
                f"max abs err {errs[tag]:.4g} (tol {tol:.3g})")
    say(f"  flash: shape {list(shape)} bf16, interpret={interpret}, "
        f"compile+run {compile_s:.1f}s")
    return {"phase": "flash", "shape": list(shape), "max_abs_err": errs,
            "interpret": interpret, "compile_s": round(compile_s, 2),
            "failed": c.failed}


# -- the smoke ---------------------------------------------------------------

def run_phase(results: list, fn, *args, **kwargs):
    """Run one phase; an exception is a failed phase with its traceback
    printed, and the remaining phases still run (a chip call is dear)."""
    from paddle_tpu.distributed import mesh as mesh_mod

    say(f"== {kwargs.pop('title')}")
    try:
        out = fn(*args, **kwargs)
    except Exception as e:               # noqa: BLE001 — reported, exit != 0
        traceback.print_exc(file=sys.stdout)
        out = {"phase": fn.__name__,
               "failed": [f"{fn.__name__} raised {type(e).__name__}: {e}"]}
    results.append(out)
    # phases are independent programs sharing one process: the trainer's
    # global mesh must not shard the next phase's server, and the phase's
    # device buffers must be gone before the next one allocates
    mesh_mod.set_global_mesh(None)
    gc.collect()
    return out


def main() -> int:
    import jax
    import jaxlib
    from paddle_tpu.core.chip import attached_chip, place_compile_cache
    from paddle_tpu.models import gpt2_345m

    try:
        device, _peaks = attached_chip()
    except (RuntimeError, ValueError) as e:
        # not a TPU (or none that starts), or one the peaks table lacks
        print(f"chip_smoke: {e}; there is no CPU mode of this script",
              file=sys.stderr)
        return 2
    cache_dir = place_compile_cache()
    # JAX's own counters: compiles that consulted the persistent cache,
    # those it answered, and entries written (compiles over the size and
    # compile-time thresholds JAX sets for keeping an entry)
    prefix = "/jax/compilation_cache/"
    cache_events = {"compile_requests_use_cache": 0, "cache_hits": 0,
                    "cache_misses": 0}

    def _on_event(event, **_kw):
        if event.startswith(prefix) and event[len(prefix):] in cache_events:
            cache_events[event[len(prefix):]] += 1

    jax.monitoring.register_event_listener(_on_event)
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    say(f"chip_smoke: platform {device['platform']}, device_kind "
        f"{device['kind']!r}, {device['count']} device(s); jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu_version}; compile cache at {cache_dir}")
    t_start = time.perf_counter()
    results: list = []
    n_dev = device["count"]

    cfg = gpt2_345m(recompute=False, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    train = dict(seq=1024, batch_per_device=8, steps=4)
    serve = dict(max_seq=1024, num_slots=8, block_size=16, min_bucket=16,
                 prompt_lens=(5, 100, 300, 1000, 40, 200), shared_len=48,
                 max_new_tokens=8)

    run_phase(results, flash_phase, (8, 1024, 16, 64),
              title="flash kernels vs oracle at the training shape")
    base = run_phase(results, train_phase, cfg, **train,
                     title="train GPT-2 345M, 8 x 1024 per chip")
    one = run_phase(results, serve_phase, "gpt:gpt2-345m", **serve,
                    title="serve GPT-2 345M, the default engine")
    run_phase(results, serve_phase, latent_model(), name="serve-latent",
              max_seq=2048, num_slots=32, block_size=16, min_bucket=256,
              prompt_lens=(200, 300, 700, 2000, 400, 260), shared_len=512,
              max_new_tokens=8, pallas_calls=3 + 2 * 2, exact=False,
              title="serve a latent-attention expert model (1 dense + 2 "
                    "expert layers at JoyAI-LLM-Flash's widths), paged")
    if n_dev >= 4:
        hyb = run_phase(
            results, train_phase, cfg, **train,
            hybrid_configs={"dp_degree": n_dev // 2, "mp_degree": 2},
            title=f"train under hybrid dp={n_dev // 2} x mp=2")
        if "losses" in hyb and "losses" in base:
            # same seed, same global batch: only the layout differs
            band = 0.1
            gap = max(abs(a - b) for a, b in
                      zip(hyb["losses"], base["losses"]))
            ok = Checks("train-hybrid").check(
                f"losses within {band} of the data-parallel run", gap <= band,
                f"max gap {gap:.4f}")
            if not ok:
                hyb["failed"].append(f"hybrid losses off by {gap:.4f}")
        run_phase(results, serve_phase, "gpt:gpt2-345m", **serve,
                  model_parallel=n_dev, expect_tokens=one.get("greedy_tokens"),
                  title=f"serve through serving_mesh({n_dev})")

    failed = [f for r in results for f in r.get("failed", [])]
    summary = {"device": device, "versions": {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version}, "cache_dir": cache_dir,
        "persistent_cache": cache_events,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "phases": results, "failed": failed}
    say(f"chip_smoke: persistent compile cache {cache_events}; total "
        f"{summary['wall_s']}s")
    for f in failed:
        say(f"chip_smoke: FAILED {f}")
    say("SMOKE_SUMMARY " + json.dumps(summary))
    say(json.dumps({"ok": not failed, "device": device}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

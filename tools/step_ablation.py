"""Decompose the bench step — on hardware by wall timing, or OFFLINE
by XLA cost analysis when no TPU is reachable.

mxu_probe.py (round 5, fixed timing) shows every GEMM family of the
compiled step sustains 85-99% MXU standalone, refuting the r3 "matmuls
at 55%" reading — so the step's gap to the ~79 ms GEMM-ideal lives
elsewhere.  On hardware this tool measures:

  full      loss + backward + AdamW      (the exact bench step)
  fwd_bwd   loss + backward, no opt      (full - fwd_bwd = optimizer)
  fwd       loss only                    (fwd_bwd - fwd   = backward)
  flash_fwd / flash_bwd                  Pallas kernel standalone at
                                         model shapes [128, 1024, 64]

Timing: 10 python-loop calls with one final sync; flash standalone uses
the mxu_probe slope method.

**Offline mode** (``--offline``, or automatic when ``JAX_PLATFORMS``
is cpu):
instead of wall timing, the SAME three programs are compiled-not-run
and decomposed analytically via :mod:`paddle_tpu.obs.hlo_cost` —
flops / bytes / HLO op mix per variant, the optimizer and backward
deltas, and a roofline step-time projection per chip spec.  That makes
the tool importable and smoke-testable in tier-1 (tests/test_train_obs)
instead of hardware-only dead code, and the cost code is the exact
code the training observatory's :class:`CostLedger` runs.

Usage:
  python tools/step_ablation.py            # on the chip, through the chip tool
  JAX_PLATFORMS=cpu python tools/step_ablation.py --offline [--full]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def time_calls(fn, *args, iters=10, warm=3):
    for _ in range(warm):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _sync(out):
    while isinstance(out, (tuple, list)):
        out = out[0]
    float(out)


def model_ablation():
    results = {}
    programs, x, y, _model, _cfg, _seq, _batch = build_ablation_programs()
    for name, fn in programs:
        seconds = time_calls(fn, x, y)
        results[name] = seconds
        print(f"{name}: {seconds*1e3:.2f} ms", flush=True)
    return results


def make_flash_runners(block_q=None, block_k=None, B=8, S=1024, H=16, D=64):
    """Jitted (run_fwd, run_bwd, q, k, v) timing harnesses for the Pallas
    flash kernel at the bench shapes: iters-step scan with per-iteration
    input perturbation (defeats CSE) and full-output sum|.| consumption
    (defeats DCE — see mxu_probe).  ``block_q`` / ``block_k`` pin the
    kernel's query block and sub-block (``flash_plan``) to time a size
    alone; left out, the sizes follow the shape."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention_kernel import (
        flash_attention_fused)

    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)

    @partial(jax.jit, static_argnums=3)
    def run_fwd(q, k, v, iters):
        def body(c, i):
            o = flash_attention_fused(q + i.astype(q.dtype) * 1e-6, k, v,
                                      causal=True, block_q=block_q,
                                      block_k=block_k)
            return c + jnp.sum(jnp.abs(o.astype(jnp.float32))), ()
        acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(iters))
        return acc

    @partial(jax.jit, static_argnums=3)
    def run_bwd(q, k, v, iters):
        def loss(q, k, v):
            o = flash_attention_fused(q, k, v, causal=True,
                                      block_q=block_q, block_k=block_k)
            return jnp.sum(jnp.abs(o.astype(jnp.float32)))

        g = jax.grad(loss, argnums=(0, 1, 2))

        def body(c, i):
            dq, dk, dv = g(q + i.astype(q.dtype) * 1e-6, k, v)
            s = (jnp.sum(jnp.abs(dq.astype(jnp.float32))) +
                 jnp.sum(jnp.abs(dk.astype(jnp.float32))) +
                 jnp.sum(jnp.abs(dv.astype(jnp.float32))))
            return c + s, ()
        acc, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(iters))
        return acc

    return run_fwd, run_bwd, q, k, v


def flash_standalone():
    from mxu_probe import slope_time

    run_fwd, run_bwd, q, k, v = make_flash_runners()

    def slope(jfn, n_lo=10, n_hi=50):
        return slope_time(lambda n: float(jfn(q, k, v, n)), n_lo, n_hi)

    return {"flash_fwd_layer": slope(run_fwd),
            "flash_fwdbwd_layer": slope(run_bwd)}


def build_ablation_programs(smoke: bool = False, batch: int = None):
    """The three ablation variants as ``(name, static_fn)`` pairs plus
    the shared example inputs — ``(programs, x, y, model, cfg, seq,
    batch)`` — used by both the hardware timing path and the offline
    cost path so the two decompositions can never diverge in WHAT they
    measure, only in HOW (wall clock vs XLA cost analysis)."""
    import paddle_tpu as paddle
    import bench

    make_step, cfg, seq, model = bench.build_bench(smoke=smoke)
    if batch is None:
        batch = 2 if smoke else 8
    amp_level = os.environ.get("PADDLE_TPU_BENCH_AMP", "O2")

    train_step, x, y = make_step(batch)

    @paddle.jit.to_static
    def fwd_bwd(x, y):
        from paddle_tpu.distributed.fault_tolerance import global_grad_norm

        with paddle.amp.auto_cast(dtype="bfloat16", level=amp_level):
            loss = model.compute_loss(x, y)
        loss.backward()
        # the grad norm CONSUMES every gradient as a program output:
        # without it, clearing the grads makes the whole backward dead
        # code — XLA DCEs it and both the wall timing and the cost
        # analysis silently measure forward-only (caught by the offline
        # cost path: fwd_bwd flops == fwd flops)
        gnorm = global_grad_norm(model.parameters())
        # ...then discard, so repeated timing calls don't pay a
        # grad-accumulate the full step doesn't have
        model.clear_gradients()
        return loss, gnorm

    @paddle.jit.to_static
    def fwd(x, y):
        with paddle.amp.auto_cast(dtype="bfloat16", level=amp_level):
            loss = model.compute_loss(x, y)
        return loss

    programs = [("full", train_step), ("fwd_bwd", fwd_bwd), ("fwd", fwd)]
    return programs, x, y, model, cfg, seq, batch


#: the chip the offline (CPU-compiled) decomposition is projected onto —
#: a ``device_kind`` of paddle_tpu.core.chip.CHIP_PEAKS
PROJECTED_CHIP = "TPU v5 lite"


def offline_ablation(smoke: bool = True, batch: int = None) -> dict:
    """CPU proxy for the hardware ablation: compile-not-run each
    variant (eval_shape state discovery + one XLA lower/compile) and
    decompose the step by XLA cost analysis instead of wall timing.

    Returns ``{"mode": "offline", "chip", "variants": {name:
    {flops, bytes_accessed, roofline_step_ms, analytic_mfu, dot,
    fusion, fingerprint}}, "deltas": {opt_*, bwd_*},
    "comm_exposure": {name: {total, overlapped, exposed,
    exposed_bytes, exposed_ms}}}`` — the flop/byte-level answer to
    "where does the step go" that needs no TPU.  ``comm_exposure``
    classifies every collective in the optimized HLO as
    overlapped-with-compute vs exposed (the schedule surface the TP
    overlap work moves) and prices the exposed bytes at the chip's
    usable ICI bandwidth."""
    import numpy as np
    from paddle_tpu.core.chip import chip_peaks
    from paddle_tpu.obs.hlo_cost import CostLedger

    programs, x, y, model, cfg, seq, batch = build_ablation_programs(
        smoke=smoke, batch=batch)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    ledger = CostLedger(chip=PROJECTED_CHIP)
    out = {"mode": "offline", "chip": ledger.chip,
           "config": {"smoke": smoke, "batch": batch, "seq": seq,
                      "n_params": n_params},
           "variants": {}}
    for name, fn in programs:
        rec = ledger.add(name, fn, x, y,
                         tokens_per_step=batch * seq, n_params=n_params)
        out["variants"][name] = {
            "flops": rec["flops"],
            "bytes_accessed": rec["bytes_accessed"],
            "transcendentals": rec["transcendentals"],
            "dot": rec["hlo_counts"]["dot"],
            "fusion": rec["hlo_counts"]["fusion"],
            "roofline_step_ms": rec["roofline_step_ms"],
            "analytic_mfu": rec["analytic_mfu"],
            "bound": rec["bound"],
            "flops_vs_6nd": rec["flops_vs_6nd"],
            "fingerprint": rec["fingerprint"],
        }
    out["comm_exposure"] = {}
    ici = chip_peaks(ledger.chip).ici_bytes_per_s
    for name, _ in programs:
        exp = ledger.programs[name].get("collective_exposure")
        if exp is None:
            continue
        out["comm_exposure"][name] = dict(
            exp, exposed_ms=round(exp["exposed_bytes"] / ici * 1e3, 6))
    v = out["variants"]
    out["deltas"] = {
        # what the optimizer adds on top of fwd+bwd, and backward on
        # top of forward — the same subtractions the hardware path does
        # on wall time, here on flops/bytes/projected roofline time
        "opt_flops": v["full"]["flops"] - v["fwd_bwd"]["flops"],
        "opt_bytes": v["full"]["bytes_accessed"]
        - v["fwd_bwd"]["bytes_accessed"],
        "opt_roofline_ms": round(v["full"]["roofline_step_ms"]
                                 - v["fwd_bwd"]["roofline_step_ms"], 6),
        "bwd_flops": v["fwd_bwd"]["flops"] - v["fwd"]["flops"],
        "bwd_bytes": v["fwd_bwd"]["bytes_accessed"]
        - v["fwd"]["bytes_accessed"],
        "bwd_roofline_ms": round(v["fwd_bwd"]["roofline_step_ms"]
                                 - v["fwd"]["roofline_step_ms"], 6),
    }
    out["fingerprint"] = ledger.fingerprint()
    return out


def main(argv=None):
    args = list(sys.argv[1:] if argv is None else argv)
    offline = "--offline" in args
    full = "--full" in args
    for known in ("--offline", "--full"):
        while known in args:
            args.remove(known)
    if args:
        print(f"step_ablation: unknown argument(s) {args}", file=sys.stderr)
        return 2
    # no TPU to time against ⇒ the offline cost decomposition is the
    # only honest answer (wall-timing XLA:CPU says nothing about MXU)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        offline = True
    if offline:
        import jax

        jax.config.update("jax_platforms", "cpu")
        print(json.dumps(offline_ablation(smoke=not full), indent=1))
        return 0
    res = model_ablation()
    res.update(flash_standalone())
    res_ms = {k: round(v * 1e3, 2) for k, v in res.items()}
    res_ms["opt_ms"] = round((res["full"] - res["fwd_bwd"]) * 1e3, 2)
    res_ms["bwd_ms"] = round((res["fwd_bwd"] - res["fwd"]) * 1e3, 2)
    res_ms["attn_total_ms"] = round(res["flash_fwdbwd_layer"] * 24 * 1e3, 2)
    print(json.dumps(res_ms, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

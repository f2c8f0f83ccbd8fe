"""MXU efficiency probe for the 345M bench's exact GEMM population.

Answers "why do the main matmuls run at ~55%?" (docs/PERF.md) with
controlled experiments on the real chip:

  A. each model GEMM shape, fwd orientation (c[1]x[0]), bf16->bf16
  B. the bwd orientations exactly as they appear in the compiled step
     (tools/dot_audit.py): dW = dot(x, dy) contracting the 8192-token
     axis on BOTH operands (c[0]x[0]), dx = dot(dy, W) contracting the
     minor axis of both (c[1]x[1]) — relayout cost shows up here
  C. f32 vs bf16 epilogues (preferred_element_type) — cast-fusion cost

Timing: the same jitted scan runs at TWO iteration counts and the slope
(t(N2)-t(N1))/(N2-N1) is taken, so constant per-call overhead (dispatch,
readback) cancels.  Each timing is the min of 3 repeats.

Usage (on the chip, through the chip tool):  python tools/mxu_probe.py
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


B, S, H, F, V = 8, 1024, 1024, 4096, 50304
M = B * S

# (name, lhs_shape, rhs_shape, (lhs_contract, rhs_contract))
# The per-layer GEMM population of GPT-2 345M fwd+bwd, in the exact
# orientations the compiled bench step uses (dot_audit.py): fwd GEMMs are
# c[1]x[0]; dW is c[0]x[0] (token axis contracted on both, no transpose
# materialized); dx is c[1]x[1] (weight used transposed in place).
SHAPES = [
    ("qkv_fwd",   (M, H), (H, 3 * H), ((1,), (0,))),
    ("attnout",   (M, H), (H, H),     ((1,), (0,))),
    ("mlp_up",    (M, H), (H, F),     ((1,), (0,))),
    ("mlp_down",  (M, F), (F, H),     ((1,), (0,))),
    ("dW_up",     (M, H), (M, F),     ((0,), (0,))),   # x · dy over tokens
    ("dW_qkv",    (M, H), (M, 3 * H), ((0,), (0,))),
    ("dx_down",   (M, H), (F, H),     ((1,), (1,))),   # dy · W^T in place
    ("dx_up",     (M, F), (H, F),     ((1,), (1,))),
    # the EXACT 3-D forms of the compiled step (dot_audit.py): activations
    # stay [B, S, H]; fwd contracts the minor dim, dW contracts BOTH major
    # dims (k = B·S split over two axes), dx contracts minor x minor
    ("fwd3d_up",  (B, S, H), (H, F),      ((2,), (0,))),
    ("dW3d_up",   (B, S, H), (B, S, F),   ((0, 1), (0, 1))),
    ("dW3d_qkv",  (B, S, H), (B, S, 3 * H), ((0, 1), (0, 1))),
    ("dx3d_down", (B, S, H), (F, H),      ((2,), (1,))),
]


def _flops(lhs_shape, rhs_shape, contract):
    lc, rc = contract
    k = int(np.prod([lhs_shape[d] for d in lc]))
    m = int(np.prod([lhs_shape[d] for d in range(len(lhs_shape))
                     if d not in lc]))
    n = int(np.prod([rhs_shape[d] for d in range(len(rhs_shape))
                     if d not in rc]))
    return 2.0 * m * n * k


def slope_time(run_n, n_lo, n_hi, repeats=3):
    """Per-iteration time from two iteration counts: constant overhead
    (dispatch, readback) cancels in the difference.  `run_n(n)`
    performs one synchronous invocation of n iterations; this helper owns
    the warm-up and best-of-repeats.  A non-positive slope means the
    measurement is noise-dominated — fail loudly instead of feeding a
    fake number downstream (the pre-rewrite probe printed >1000 TF/s)."""
    def timed(iters):
        run_n(iters)                         # warm/compile
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run_n(iters)
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo, t_hi = timed(n_lo), timed(n_hi)
    slope = (t_hi - t_lo) / (n_hi - n_lo)
    if slope <= 0:
        raise RuntimeError(
            f"non-positive slope ({t_lo*1e3:.2f} ms @ {n_lo} vs "
            f"{t_hi*1e3:.2f} ms @ {n_hi}): measurement noise-dominated, "
            f"rerun on a quiet host")
    return slope


def bench_gemm(jax, jnp, lhs_shape, rhs_shape, contract, out_dtype,
               n_lo=40, n_hi=200, repeats=3):
    from functools import partial

    from jax import lax

    key = jax.random.PRNGKey(0)
    lhs = jax.random.normal(key, lhs_shape, jnp.bfloat16)
    rhs = jax.random.normal(key, rhs_shape, jnp.bfloat16)

    @partial(jax.jit, static_argnums=2)
    def run(lhs, rhs, iters):
        def body(carry, i):
            l = lhs + i.astype(jnp.bfloat16) * 1e-6   # defeat CSE
            o = lax.dot_general(
                l, rhs, (contract, ((), ())),
                preferred_element_type=out_dtype)
            # consume ALL of o through a non-algebraic reduction: a plain
            # slice/linear readout lets XLA DCE the dot down to one row
            # (observed: every shape "ran" at >1000 TF/s before this)
            return carry + jnp.sum(jnp.abs(o.astype(jnp.float32))), ()

        acc, _ = lax.scan(body, jnp.float32(0), jnp.arange(iters))
        return acc

    per_iter = slope_time(lambda n: float(run(lhs, rhs, n)),
                          n_lo, n_hi, repeats)
    # no consume-read correction: the sum|o| reduce fuses into the GEMM
    # epilogue (and may even elide the o write), so raw slope IS the GEMM
    fl = _flops(lhs_shape, rhs_shape, contract)
    return per_iter, fl / per_iter


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.chip import attached_chip

    chip, peaks = attached_chip()        # a TPU from the peaks table or an error
    peak = peaks.bf16_flops_per_s
    print(f"device: {chip['kind']}, published bf16 peak {peak/1e12:.0f} TF/s")
    print(f"{'gemm':>10} {'orient':>10} {'epilogue':>8} {'ms':>8} "
          f"{'TF/s':>8} {'MXU%':>6}")
    for name, a, b, c in SHAPES:
        orient = f"c{list(c[0])}x{list(c[1])}".replace(" ", "")
        for out_dtype, tag in ((jnp.bfloat16, "bf16"), (jnp.float32, "f32")):
            # one noisy shape must not abort a scarce hardware window
            try:
                dt, fs = bench_gemm(jax, jnp, a, b, c, out_dtype)
            except RuntimeError as e:
                print(f"{name:>10} {orient:>10} {tag:>8}  noise/err: {e}",
                      flush=True)
                continue
            print(f"{name:>10} {orient:>10} {tag:>8} {dt*1e3:>8.3f} "
                  f"{fs/1e12:>8.1f} {100*fs/peak:>5.1f}%", flush=True)


if __name__ == "__main__":
    main()

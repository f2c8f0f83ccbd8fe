"""Compiled pipeline schedule: microbatch pipeline as ONE XLA program over
the "pipe" mesh axis.

Reference parity: PipelineParallel.forward_backward_pipeline
(fleet/meta_parallel/pipeline_parallel.py:82) — startup/steady/cooldown
loops exchanging activations over send_v2/recv_v2 between stage processes.

TPU-native design (SURVEY.md §7 "hard parts"): there are no stage
processes.  The decoder stack's per-layer parameters are stacked to
[n_stages, layers_per_stage, ...] and sharded over "pipe"; a
`shard_map` manual only on the pipe axis runs a `lax.scan` over
M + P − 1 ticks, each tick applying the stage's layers and rotating
activations with `lax.ppermute` (the ICI-native p2p replacing
send_v2/recv_v2).  TP/DP/ZeRO axes stay *auto* — GSPMD partitions inside
the pipeline body, so mp×pp×dp×sharding compose in one program.

Schedule semantics vs the reference's 1F1B (pipeline_parallel.py:82-147):
the backward pipeline here is jax.vjp of the scan — a reverse scan whose
ppermutes are the transposed forward rotation.  Its *bubble* fraction,
(P−1)/(M+P−1), is identical to 1F1B's (1F1B does not reduce the bubble,
only the in-flight activation count).  1F1B's *memory* bound (≤P live
microbatches instead of all M) is matched differently: each tick's stage
body is rematerialized (`jax.checkpoint`), so the only cross-tick state
the backward needs is the per-tick stage INPUT (size ∝ microbatch), and
total live activations stay ∝ total-batch — independent of M — rather
than M × per-stage activations.  tests/test_pipeline.py asserts this with
compiled memory statistics.

Non-uniform stacks run sequentially: a lax.switch-based per-stage
dispatch was prototyped and removed because jax 0.9.0 computes wrong
gradients for lax.switch under shard_map varying-manual-axes (forward
exact, backward corrupt; dynamic-index select is exact — pinned by
tests/test_pipeline.py::TestJaxSwitchVmaAD).
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ....core import autograd
from ....core import rng as rng_mod
from ....core.dispatch import apply_op
from ....core.tensor import Tensor
from ....nn.layer_base import Layer


def layer_param_leaves(layer: Layer) -> List[Tensor]:
    """Deterministic leaf order: parameters then buffers, name-sorted."""
    leaves = [p for _, p in sorted(layer.named_parameters())]
    leaves += [b for _, b in sorted(layer.named_buffers())]
    return leaves


def structure_signature(layer: Layer):
    return tuple((name, tuple(t.shape), str(t.dtype))
                 for name, t in sorted(layer.named_parameters())) + \
        tuple((name, tuple(t.shape), str(t.dtype))
              for name, t in sorted(layer.named_buffers()))


def _pipe_varying(x):
    """Mark an array pipe-varying for the shard_map carry."""
    return jax.lax.pcast(x, ("pipe",), to="varying")


def _psum_pipe_f32(x):
    """psum over "pipe" with the reduction carried out in f32.

    Sub-f32 all-reduces over pipe are forbidden: XLA CPU's bf16
    AllReducePromotion pass CHECK-crashes ("Invalid binary instruction
    opcode copy") when layout assignment has inserted a root copy into the
    psum's reduction computation — which it does for the shard_map
    `psum_invariant` regions this schedule generates.  An f32 all-reduce is
    never touched by that pass (and is also the numerically safer
    accumulation); the cast pair is fused away by XLA on TPU.
    """
    dt = x.dtype
    if dt in (jnp.float32, jnp.float64):
        return jax.lax.psum(x, "pipe")
    return jax.lax.psum(x.astype(jnp.float32), "pipe").astype(dt)


@jax.custom_vjp
def _enter_pipe(x):
    """Invariant→pipe-varying cast whose backward reduces in f32.

    The default transpose of reading a pipe-invariant array inside the
    pipeline body is a bf16 `psum_invariant` over "pipe" — the exact
    all-reduce shape that CHECK-crashes XLA CPU (see _psum_pipe_f32).
    Routing the input through this custom_vjp keeps the forward free
    (a vma cast, no collective) and makes the cotangent reduction f32.
    """
    return _pipe_varying(x)


def _enter_pipe_fwd(x):
    return _pipe_varying(x), None


def _enter_pipe_bwd(_, g):
    return (_psum_pipe_f32(g),)


_enter_pipe.defvjp(_enter_pipe_fwd, _enter_pipe_bwd)


def _template_apply(template: Layer, leaf_arrays, x_arr):
    """Run template.forward on raw arrays via payload swap (tape off: the
    pipeline primal is differentiated as one op)."""
    leaves = layer_param_leaves(template)
    saved = [(t, t._data) for t in leaves]
    try:
        for t, a in zip(leaves, leaf_arrays):
            t._data = a
        with autograd.no_grad():
            out = template(Tensor._wrap(x_arr))
    finally:
        for t, a in saved:
            t._data = a
    return out._value() if isinstance(out, Tensor) else out


def _scan_pipeline(stage_fn, xs, n_stages, n_micro, mesh, key_arr,
                   extra_flat, extra_specs):
    """Common scan-over-ticks pipeline driver.

    stage_fn(stage, t, key_l, x_in, extras) -> y runs one stage's layers
    for one tick; it is rematerialized so the backward holds only per-tick
    stage inputs.  The last stage's drained outputs come back replicated
    via a masked psum.  (A pipe-stacked P("pipe") output + static slice —
    one broadcast-from-owner instead of an all-reduce — was tried and
    reverted: GSPMD lowers the slice to an all-reduce whose reduction
    computation is `copy`, and XLA CPU's bf16 AllReducePromotion pass
    CHECK-crashes cloning it ("Invalid binary instruction opcode copy"),
    killing every bf16 test on the virtual CPU mesh.)"""

    def inner(key_l, xs_full, *extras):
        stage = jax.lax.axis_index("pipe")
        # enter the manual pipe region through the f32-backward cast so no
        # bf16 psum_invariant is ever emitted over "pipe"
        xs_full = _enter_pipe(xs_full)
        pad = jnp.zeros((n_stages - 1,) + xs_full.shape[1:], xs_full.dtype)
        pad = _pipe_varying(pad)
        ticks = jnp.concatenate([xs_full, pad], axis=0)
        state0 = jnp.zeros(xs_full.shape[1:], xs_full.dtype)
        # the carry becomes pipe-varying after the first ppermute; its
        # initial value must carry the same vma type for scan
        state0 = _pipe_varying(state0)

        # prevent_cse=False is the documented setting for remat inside
        # scan: it lets XLA hoist/CSE loop-invariant slices (per-stage
        # param gathers) instead of saving them per tick
        body = jax.checkpoint(
            lambda x_in, t: stage_fn(stage, t, key_l, x_in, extras),
            prevent_cse=False)

        def tick(carry, inp):
            prev_y, t = carry
            # the micro-batch boundary ppermute is issued at tick ENTRY
            # (on the previous tick's output, carried raw) rather than
            # after the compute that produced it: the hop is then live
            # while this tick's stage GEMMs run, instead of serializing
            # at the tick boundary.  Values are identical — the permute
            # commutes across the carry (permute(zeros) == zeros seeds
            # tick 0), so the schedule change is bitwise-neutral.
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(prev_y, "pipe", perm)
            x_in = jnp.where(stage == 0, inp, state)
            y = body(x_in, t)
            # only the last stage's y is pipeline output
            out_t = jnp.where(stage == n_stages - 1, y, jnp.zeros_like(y))
            return (y, t + 1), out_t

        (_, _), ys = jax.lax.scan(tick, (state0, jnp.int32(0)), ticks)
        ys = ys[n_stages - 1:]                       # drop fill ticks
        return _psum_pipe_f32(ys)                    # replicate output

    in_specs = (P(), P()) + tuple(extra_specs)
    inner_f = jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names={"pipe"})
    return inner_f(key_arr, xs, *extra_flat)


def _scan_pipeline_interleaved(chunk_fn, xs, n_stages, n_micro, n_virtual,
                               mesh, key_arr, extra_flat, extra_specs):
    """Interleaved (virtual-stage) schedule — one XLA scan.

    Reference contract: PipelineLayer(num_virtual_pipeline_stages=v) +
    the Megatron interleaved 1F1B (the reference only ships plain 1F1B;
    interleaving is a beyond-reference bubble reduction).

    Construction: the layer stack is cut into v·P chunks; device i owns
    chunks {i, P+i, …, (v−1)P+i}.  Microbatches are injected in bursts of
    P (burst b starts at tick b·v·P); every tick each device runs ONE
    chunk and the activation ppermutes one hop.  At tick t device i
    solves::

        r = (t − i) mod P          # burst offset of its active microbatch
        j = (t − r) mod v·P        # the global chunk it must run
        b = (t − r) // (v·P)       # which burst
        m = b·P + r                # microbatch id (valid iff 0 ≤ b < M/P)
        c = j // P                 # local chunk index (j ≡ i (mod P))

    Total ticks v·M + P − 1, so the bubble is (P−1)/(v·M+P−1) versus
    1F1B's (P−1)/(M+P−1), at the cost of (v−1) extra ppermute hops per
    microbatch — the interleaving trade.  Memory matches the uniform
    schedule: the tick body is rematerialized, so the backward holds one
    per-tick chunk input.
    """
    vP = n_virtual * n_stages
    n_ticks = n_virtual * n_micro + n_stages - 1

    def inner(key_l, xs_full, *extras):
        stage = jax.lax.axis_index("pipe")
        xs_full = _enter_pipe(xs_full)
        state0 = _pipe_varying(jnp.zeros(xs_full.shape[1:], xs_full.dtype))

        body = jax.checkpoint(
            lambda x_in, c, t: chunk_fn(stage, c, t, key_l, x_in, extras),
            prevent_cse=False)

        def tick(carry, t):
            prev_y = carry
            # boundary ppermute issued at tick entry (see _scan_pipeline)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(prev_y, "pipe", perm)
            r = (t - stage) % n_stages
            j = (t - r) % vP
            b = (t - r) // vP
            m = b * n_stages + r
            valid = (b >= 0) & (b < n_micro // n_stages)
            c = j // n_stages
            inject = (stage == 0) & (j == 0) & valid
            m_safe = jnp.clip(m, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(
                xs_full, m_safe, axis=0, keepdims=False)
            x_in = jnp.where(inject, fresh, state)
            y = body(x_in, c, t)
            emit = (stage == n_stages - 1) & (j == vP - 1) & valid
            out_t = jnp.where(emit, y, jnp.zeros_like(y))
            return y, out_t

        ys = jax.lax.scan(tick, state0, jnp.arange(n_ticks,
                                                   dtype=jnp.int32))[1]
        # microbatch m finishes at tick (m//P)·v·P + (m%P) + v·P − 1
        mm = jnp.arange(n_micro)
        finish = (mm // n_stages) * vP + (mm % n_stages) + vP - 1
        ys = jnp.take(ys, finish, axis=0)
        return _psum_pipe_f32(ys)

    in_specs = (P(), P()) + tuple(extra_specs)
    inner_f = jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=P(),
        axis_names={"pipe"})
    return inner_f(key_arr, xs, *extra_flat)


def pipeline_apply(template: Layer, per_layer_leaves: Sequence[Sequence[Tensor]],
                   x: Tensor, n_stages: int, n_micro: int, mesh,
                   n_virtual: int = 1) -> Tensor:
    """Run a uniform layer stack over the pipe axis.

    per_layer_leaves: [n_layers][n_leaf] framework Tensors (the real
    Parameters — their .grad receives the pipeline's backward).
    x: [B, ...] activations entering the stack.  B must divide n_micro.
    n_virtual > 1 selects the interleaved (virtual-stage) schedule:
    n_stages*n_virtual must divide n_layers, and n_stages must divide
    n_micro.
    """
    n_layers = len(per_layer_leaves)
    n_leaf = len(per_layer_leaves[0])
    n_chunks = n_stages * max(n_virtual, 1)
    if n_layers % n_chunks:
        raise ValueError(
            f"{n_layers} layers do not divide {n_stages} stages x "
            f"{n_virtual} virtual chunks")
    if n_virtual > 1 and n_micro % n_stages:
        raise ValueError(
            f"interleaved schedule needs microbatches ({n_micro}) divisible "
            f"by stages ({n_stages})")
    k_chunk = n_layers // n_chunks
    flat_params: List[Tensor] = [t for layer in per_layer_leaves for t in layer]

    gen_state = rng_mod.default_generator()._state
    region_key = Tensor._wrap(jax.random.key_data(rng_mod.next_key()))

    def primal(x_arr, key_arr, *leaf_arrays):
        B = x_arr.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} does not divide {n_micro} microbatches")
        mb = B // n_micro
        xs = x_arr.reshape((n_micro, mb) + x_arr.shape[1:])

        if n_virtual <= 1:
            # stack leaves → [n_stages, k_chunk, ...] sharded on pipe
            stacked = []
            for j in range(n_leaf):
                s = jnp.stack([leaf_arrays[i * n_leaf + j]
                               for i in range(n_layers)], axis=0)
                s = s.reshape((n_stages, k_chunk) + s.shape[1:])
                stacked.append(s)

            def stage_fn(stage, t, key_l, x_in, stacked_local):
                y = x_in
                saved_state = gen_state._data
                try:
                    for k in range(k_chunk):
                        arrs = [lv[0, k] for lv in stacked_local]
                        # per-(tick, local-layer) RNG stream for dropout
                        kk = jax.random.fold_in(
                            jax.random.wrap_key_data(key_l),
                            t * n_layers + stage * k_chunk + k)
                        gen_state._data = jax.random.key_data(kk)
                        y = _template_apply(template, arrs, y)
                finally:
                    gen_state._data = saved_state
                return y

            extra_specs = tuple(P("pipe") for _ in range(n_leaf))
            ys = _scan_pipeline(stage_fn, xs, n_stages, n_micro, mesh,
                                key_arr, tuple(stacked), extra_specs)
            return ys.reshape((B,) + ys.shape[2:])

        # interleaved: chunk j = c*P + i lives at stacked[i, c]
        stacked = []
        for j in range(n_leaf):
            s = jnp.stack([leaf_arrays[i * n_leaf + j]
                           for i in range(n_layers)], axis=0)
            s = s.reshape((n_virtual, n_stages, k_chunk) + s.shape[1:])
            s = jnp.swapaxes(s, 0, 1)      # [P, v, k_chunk, ...]
            stacked.append(s)

        def chunk_fn(stage, c, t, key_l, x_in, stacked_local):
            y = x_in
            saved_state = gen_state._data
            try:
                for k in range(k_chunk):
                    # local leaves [1, v, k_chunk, ...] — dynamic chunk
                    # select (exact AD, unlike lax.switch; see module note)
                    arrs = [jax.lax.dynamic_index_in_dim(
                        lv[0], c, axis=0, keepdims=False)[k]
                        for lv in stacked_local]
                    layer_id = (c * n_stages + stage) * k_chunk + k
                    kk = jax.random.fold_in(
                        jax.random.wrap_key_data(key_l),
                        t * n_layers + layer_id)
                    gen_state._data = jax.random.key_data(kk)
                    y = _template_apply(template, arrs, y)
            finally:
                gen_state._data = saved_state
            return y

        extra_specs = tuple(P("pipe") for _ in range(n_leaf))
        ys = _scan_pipeline_interleaved(
            chunk_fn, xs, n_stages, n_micro, n_virtual, mesh, key_arr,
            tuple(stacked), extra_specs)
        return ys.reshape((B,) + ys.shape[2:])

    return apply_op("pipeline_scan_remat", primal,
                    [x, region_key] + flat_params)

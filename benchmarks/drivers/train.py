"""Driver of a training cell (``kind: train``).

Order of a run: set-up builds the one compiled step with its state, hands it
the seeded weights, and drives it through the job's first steps by the
window's own call and feed, keeping on the host what ``correct`` will
compare: their losses, the first gradient as AdamW got it (its first moment
after one step), and the norms of the parameters' change after the steps.
The same object then runs the window.  Then the memory peak is read (the
program's own), the program's state is freed, and the plain reference follows
those first steps from the seed; the program's numbers are held against it.
The reference's time is not set-up.

The recipe is ``bench.build_train_step``'s, rebuilt through the same public
calls: ``fleet.init`` -> ``distributed_model`` -> ``AdamW`` -> ``amp.decorate``
O2 -> ``jit.to_static`` step with bf16 autocast and ``model.compute_loss``.
"""
from __future__ import annotations

import collections
import math
import statistics
import time

import numpy as np

from benchmarks.adapters import _load
from benchmarks.harness import trace_reduce, weights
from benchmarks.harness.context import (Checks, CompileCounter, GcWatch,
                                        Spans, settle_heap)
from benchmarks.harness.manifest import load_module

AMP_LEVEL = "O2"         # bf16 parameters and compute, f32 masters and moments
CHECKED_STEPS = 3        # the first steps that the reference follows
WARM_STEPS = 5           # more steps before the window (step 2 lowers anew)
RUN_AHEAD = 2            # steps dispatched ahead of the last loss read
REFERENCE_ROWS = 4       # rows of a batch per call of the reference
TRACE_SECONDS = 4.0      # the traced slice of a ``--trace 1`` window


def _norms(leaves) -> dict:
    """``{key: l2 norm}`` of ``(key, array)`` pairs, one host pull."""
    import jax.numpy as jnp

    keys, vals = [], []
    for k, a in leaves:
        keys.append(k)
        vals.append(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
    if not keys:
        return {}
    return dict(zip(keys, np.asarray(jnp.stack(vals)).tolist()))


def reference_steps(ref, adapter, d, shapes, seed, x, y, opt: dict,
                    steps: int, rows: int, control: bool = False,
                    on_first_grad=None) -> dict:
    """The job's first ``steps`` AdamW steps in plain float32 from the seeded
    weights, gradients accumulated over blocks of ``rows`` rows.  Returns each
    step's loss and, by program leaf, the first gradient's norm and the norm
    of the parameters' change; ``on_first_grad`` is called with the first
    gradient's ``(key, array)`` pairs while it is on the device, and what it
    returns is handed back as ``first_grad``.  Buffers are donated from step
    to step, so the reference holds weights, two moments and two gradient
    trees at most."""
    import jax
    import jax.numpy as jnp

    B, S = x.shape[1], x.shape[2]
    n_tok = float(B * S)
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=0)
    mean = jax.jit(lambda g: jax.tree_util.tree_map(lambda a: a / n_tok, g),
                   donate_argnums=0)
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["weight_decay"]

    def adamw(w, g, m, v, t):
        def one(p, gi, mi, vi):
            mi = b1 * mi + (1 - b1) * gi
            vi = b2 * vi + (1 - b2) * jnp.square(gi)
            m_hat, v_hat = mi / (1 - b1 ** t), vi / (1 - b2 ** t)
            p = p * (1 - lr * wd) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
            return p, mi, vi
        out = {k: one(w[k], g[k], m[k], v[k]) for k in w}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    adamw = jax.jit(adamw, donate_argnums=(0, 2, 3))
    w = weights.make(shapes, seed, jnp.float32)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, first, grad_norms, step_s = [], None, None, []
    for k in range(steps):
        t_step = time.perf_counter()
        total, g = 0.0, None
        for r in range(0, B, rows):
            l, gr = ref.grad_rows(w, jnp.asarray(x[k, r:r + rows]),
                                  jnp.asarray(y[k, r:r + rows]), d, control)
            total += float(l)
            g = gr if g is None else add(g, gr)
            del gr
        losses.append(total / n_tok)
        g = mean(g)
        if k == 0:
            grad_norms = _norms(adapter.program_leaves(g, d))
            if on_first_grad is not None:
                first = on_first_grad(adapter.program_leaves(g, d))
        w, m, v = adamw(w, g, m, v, jnp.float32(k + 1))
        del g
        step_s.append(time.perf_counter() - t_step)
    del m, v
    w0 = weights.make(shapes, seed, jnp.float32)
    delta_norms = _norms(adapter.program_leaves(
        jax.tree_util.tree_map(jnp.subtract, w, w0), d))
    return {"losses": losses, "first_grad": first, "grad_norms": grad_norms,
            "delta_norms": delta_norms, "step_s": step_s}


def diff_norms(leaves, other: dict) -> dict:
    """``{key: l2 norm of leaf - other[key]}`` for ``(key, array)`` pairs, on
    the device, a leaf at a time; a key that ``other`` lacks is left out."""
    import jax.numpy as jnp

    return _norms((k, jnp.asarray(a, jnp.float32)
                   - jnp.asarray(other[k], jnp.float32))
                  for k, a in leaves if k in other)


def worst_ratio(num: dict, ref: dict):
    """The widest ``num[key]`` over the reference's norm of that leaf or of
    the median leaf, whichever is larger; a key that ``num`` lacks reads nan.
    With ``num`` the norms of ``program gradient - reference gradient`` this
    is first-order in a rounding error, where a gap between two norms is
    second-order.  Returns ``(ratio, key)``."""
    floor = statistics.median(ref.values())
    worst, where = 0.0, None
    for k, r in ref.items():
        gap = num.get(k, float("nan")) / max(r, floor)
        if not gap <= worst:        # also catches nan
            worst, where = gap, k
    return worst, where


def worst_leaf(prog: dict, ref: dict):
    """The widest gap between the program's norm of a leaf and the
    reference's, over the reference's norm of that leaf or of the median
    leaf, whichever is larger.  Returns ``(gap, key)``."""
    return worst_ratio({k: abs(prog.get(k, 0.0) - r) for k, r in ref.items()},
                       ref)


def build_program(ctx, adapter, d, tree):
    """The program's compiled step with its state: returns
    ``(train_step, model, opt)``."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet

    tp = ctx.mix["trainer"]
    strategy = paddle.distributed.DistributedStrategy()
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    model = fleet.distributed_model(adapter.build_model(ctx.config))
    _load.load(model, adapter, tree, d)
    o = tp["optimizer"]
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters()))
    model, opt = paddle.amp.decorate(model, optimizers=opt, level=AMP_LEVEL)

    @paddle.jit.to_static
    def train_step(x, y):
        with paddle.amp.auto_cast(dtype="bfloat16", level=AMP_LEVEL):
            loss = model.compute_loss(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return train_step, model, opt


def _opt_state(model, opt, acc: str):
    """``(state_dict key, array)`` of accumulator ``acc`` per parameter; a
    parameter without one yields nothing."""
    state = opt.state_dict()
    for key, p in model.state_dict().items():
        t = state.get(f"{p.name}/{acc}")
        if t is not None:
            yield key, t._value()


def program_run(ctx, adapter, d, shapes, x, y) -> dict:
    """Set-up, the checked first steps and the window of the program's one
    compiled step.  Hands back host values alone, so that all of the
    program's device state is dropped when it returns."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle

    from benchmarks.harness import peaks as pk

    tp = ctx.mix["trainer"]
    B, S = x.shape[1], x.shape[2]
    compiles = CompileCounter()
    spans = Spans()
    t_phase = time.perf_counter()

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        ctx.say(f"set-up phase {name}: {now - t_phase:.2f}s")
        t_phase = now
        return now

    # -- set-up: one compiled step with its state ---------------------------
    tree = weights.make(shapes, ctx.seed, jnp.float32)
    phase("seeded weights")
    train_step, model, opt = build_program(ctx, adapter, d, tree)
    del tree
    phase("model, weights handed over, optimizer")
    parts = {"train_step": train_step, "model": model, "opt": opt}
    if ctx.sabotage is not None:
        ctx.sabotage(parts)
    train_step = parts["train_step"]
    xs = [paddle.to_tensor(x[i]) for i in range(x.shape[0])]
    ys = [paddle.to_tensor(y[i]) for i in range(y.shape[0])]
    n_b = len(xs)

    def step(i):
        return train_step(xs[i % n_b], ys[i % n_b])

    # what ``correct`` compares is read here and kept on the host; reading it
    # is the check's time, not set-up
    got = {"losses": [], "grad_norms": {}, "grads": {}, "delta_norms": {}}
    b1 = tp["optimizer"]["beta1"]
    check_s = 0.0
    for k in range(CHECKED_STEPS):
        got["losses"].append(float(step(k)))
        t_read = phase(f"step {k + 1}")
        if k == 0:
            m1 = [(key, a / (1.0 - b1))
                  for key, a in _opt_state(model, opt, "moment1")]
            got["grad_norms"] = _norms(m1)
            got["grads"] = dict(zip([key for key, _ in m1],
                                    jax.device_get([a for _, a in m1])))
            del m1
            check_s += phase("first gradient read to the host") - t_read
    t_read = time.perf_counter()
    masters = dict(_opt_state(model, opt, "master_weight"))
    now = {key: masters.get(key, p._value())
           for key, p in model.state_dict().items()}
    got["delta_norms"] = _norms(
        (key, now[key].astype(jnp.float32) - w0) for key, w0 in
        adapter.program_leaves(weights.make(shapes, ctx.seed, jnp.float32),
                               d))
    del masters, now
    check_s += phase("parameters' change read") - t_read
    for k in range(CHECKED_STEPS, CHECKED_STEPS + WARM_STEPS):
        last = step(k)
    float(last)
    phase(f"{WARM_STEPS} more warm steps")
    i_next = CHECKED_STEPS + WARM_STEPS
    settle_heap()
    setup_s = time.perf_counter() - ctx.t_start - check_s
    compiles_before = compiles.count

    # -- the window ---------------------------------------------------------
    profiler = None
    if ctx.trace:
        profiler = trace_reduce.Profiler(
            ctx.out_dir, time.perf_counter(),
            start_after=min(2.0, ctx.seconds / 4),
            length=min(TRACE_SECONDS, ctx.seconds / 2))
    pending = collections.deque()
    window_losses, step_ends = [], []
    gc_watch = GcWatch().start()
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < ctx.seconds:
        if profiler is not None:
            profiler.maybe(time.perf_counter())
        with spans.span("train_step"):
            pending.append(step(i_next + n))
        n += 1
        if len(pending) > RUN_AHEAD:
            with spans.span("wait_loss"):
                window_losses.append(float(pending.popleft()))
            step_ends.append(time.perf_counter())
    with spans.span("wait_loss"):
        while pending:
            window_losses.append(float(pending.popleft()))
            step_ends.append(time.perf_counter())
    t1 = time.perf_counter()
    ctx.say(gc_watch.stop())
    trace = profiler.finish() if profiler is not None else None
    elapsed = t1 - t0
    gaps = np.diff(step_ends) if len(step_ends) > 2 else np.array([elapsed])
    in_window = compiles.count - compiles_before
    ctx.say(f"window: {n} steps of {B}x{S} in {elapsed:.3f}s; step gap ms "
            f"median {1e3 * float(np.median(gaps)):.2f} max "
            f"{1e3 * float(gaps.max()):.2f}; compiles in window "
            f"{in_window}; loss "
            f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}")
    return {"got": got, "setup_s": setup_s, "check_read_s": check_s,
            "steps": n, "window_s": elapsed, "window_losses": window_losses,
            "step_gap_s": float(np.median(gaps)), "trace": trace,
            "spans": spans.rows, "compiles_in_window": in_window,
            "memory_peak_bytes": pk.memory_peak_bytes()}


def run(ctx) -> dict:
    import gc

    import jax

    from benchmarks.harness import peaks as pk

    tp, lim = ctx.mix["trainer"], ctx.limits
    family = ctx.config["family"]
    ref = load_module("references", family)
    adapter = load_module("adapters", family)
    d = ref.dims(ctx.config)
    B, S = int(tp["batch_per_chip"]) * ctx.chips, int(tp["seq"])
    gen = load_module("generators", ctx.mix["generator"])
    x, y = gen.generate(ctx.mix["params"], ctx.seed, vocab=d["vocab"],
                        batch=B, seq=S)
    shapes = ref.weight_shapes(ctx.config)
    checks = Checks(ctx.say)

    prog = program_run(ctx, adapter, d, shapes, x, y)
    got, n, elapsed = prog["got"], prog["steps"], prog["window_s"]
    tokens_per_s_chip = n * B * S / elapsed / ctx.chips
    steady = B * S / prog["step_gap_s"] / ctx.chips
    bad = sum(1 for l in prog["window_losses"] if not math.isfinite(l))

    # -- the program's state is gone; the reference follows the same steps --
    jax.clear_caches()
    gc.unfreeze()           # set-up froze the heap; the program is in it
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    ctx.say(f"program's memory peak {prog['memory_peak_bytes']}; "
            f"{live} bytes still live on the device before the reference")
    t_ref = time.perf_counter()
    keep = {}

    def first_grad(leaves):
        if not ctx.control:
            return diff_norms(leaves, got["grads"])
        keep.update((k, np.asarray(a)) for k, a in leaves)
        return diff_norms(keep.items(), got["grads"])

    want = reference_steps(ref, adapter, d, shapes, ctx.seed, x, y,
                           tp["optimizer"], CHECKED_STEPS, REFERENCE_ROWS,
                           on_first_grad=first_grad)
    ref_s = time.perf_counter() - t_ref
    ctx.say(f"reference: {CHECKED_STEPS} steps in {ref_s:.1f}s (not set-up; "
            f"seconds by step {[round(t, 1) for t in want['step_s']]}), "
            f"losses {want['losses']}; memory peak with it "
            f"{pk.memory_peak_bytes()}")

    def compare(name, losses, grad_norms, dir_norms, delta_norms):
        loss_rel = max(abs(a - b) / b for a, b in zip(losses, want["losses"]))
        g, g_at = worst_leaf(grad_norms, want["grad_norms"])
        gd, gd_at = worst_ratio(dir_norms, want["grad_norms"])
        dl, d_at = worst_leaf(delta_norms, want["delta_norms"])
        ctx.say(f"{name}: loss_rel {loss_rel:.6g} grad_norm_rel {g:.6g} at "
                f"{g_at} grad_dir_rel {gd:.6g} at {gd_at} delta_norm_rel "
                f"{dl:.6g} at {d_at}")
        return loss_rel, g, gd, dl

    numbers = compare("program", got["losses"], got["grad_norms"],
                      want["first_grad"], got["delta_norms"])
    ctx.say(f"program losses {got['losses']}")
    for name, value in zip(("loss_rel", "grad_norm_rel", "grad_dir_rel",
                            "delta_norm_rel"), numbers):
        checks.le(name, value, lim[name])
    checks.le("loss_last_over_first",
              prog["window_losses"][-1] / got["losses"][0], 1.0)
    control_numbers = None
    if ctx.control:
        c = reference_steps(
            ref, adapter, d, shapes, ctx.seed, x, y, tp["optimizer"],
            CHECKED_STEPS, REFERENCE_ROWS, control=True,
            on_first_grad=lambda leaves: diff_norms(leaves, keep))
        control_numbers = compare("control", c["losses"], c["grad_norms"],
                                  c["first_grad"], c["delta_norms"])
    return {
        "checks": checks, "attempted": n, "failed": bad,
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip,
                       "setup_s": prog["setup_s"]},
        "memory_peak_bytes": prog["memory_peak_bytes"],
        "trace": prog["trace"], "spans": prog["spans"], "counters": {},
        "facts": {"kind": "train", "batch": B, "seq": S, "steps": n,
                  "window_s": elapsed, "dims": d,
                  "tokens_per_s_chip": tokens_per_s_chip,
                  "steady_tokens_per_s_chip": steady,
                  "compiles_in_window": prog["compiles_in_window"],
                  "reference_s": ref_s, "check_read_s": prog["check_read_s"],
                  "control": control_numbers, "live_bytes_after_free": live,
                  "program_numbers": list(numbers)},
    }

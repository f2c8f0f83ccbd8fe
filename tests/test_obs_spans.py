"""The program's one span primitive (``paddle_tpu.obs.spans``), the surfaces
that go through it, the spans of ``Engine.step`` and of a program-cache miss,
and the device-side names a compiled program carries (module name, kernel
names, scopes)."""
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.obs import spans
from paddle_tpu.obs.flight import FlightRecorder
from paddle_tpu.obs.train import StepTimeline
from paddle_tpu.serving import Engine
from paddle_tpu.serving.tracing import RequestTracer

from chip_programs import (attention_layer_program,  # noqa: F401
                           custom_call_lines, engine_program, kernel_lines,
                           load_patterns, moves_around_kernels, one_chip)

NAME, START, END, PARENT, ATTRS, SID = range(6)


def rows_since(t):
    return spans.snapshot(since=t)


@pytest.fixture(autouse=True, scope="module")
def no_leftover_global_mesh():
    """The programs here are single-device ones: a global mesh that another
    file's test left set on this worker (no file resets it) would put its
    devices into their sharding constraints and take the train step off the
    streamed CE."""
    from paddle_tpu.distributed import mesh as mesh_mod

    left = mesh_mod.get_global_mesh()
    mesh_mod.set_global_mesh(None)
    yield
    mesh_mod.set_global_mesh(left)


# -- the primitive -----------------------------------------------------------

def test_nesting_gives_parent_pointers_and_children_close_first():
    t = spans.clock()
    with spans.span("outer", k=1) as outer:
        with spans.span("inner") as inner:
            assert spans._stack()[-1] is inner
        spans.mark("moment", n=2)
    got = rows_since(t)
    assert [r[NAME] for r in got] == ["inner", "moment", "outer"]
    by = {r[NAME]: r for r in got}
    assert by["outer"][PARENT] is None
    assert by["inner"][PARENT] == by["moment"][PARENT] == outer.sid
    assert by["outer"][SID] == outer.sid and by["outer"][ATTRS] == {"k": 1}
    assert by["outer"][START] <= by["inner"][START] <= by["inner"][END] \
        <= by["outer"][END]
    assert by["moment"][START] == by["moment"][END]
    assert (outer.t0, outer.t1) == (by["outer"][START], by["outer"][END])
    assert spans._stack() == []


def test_attributes_set_until_the_span_closes_reach_its_row():
    t = spans.clock()
    with spans.span("late", a=1) as sp:
        sp.set(b=2)
        sp.attrs["c"] = 3
    assert rows_since(t)[-1][ATTRS] == {"a": 1, "b": 2, "c": 3}


def test_the_ring_is_one_bounded_deque():
    assert spans._ring.maxlen == spans.RING_ROWS >= 100_000
    for _ in range(spans.RING_ROWS + 10):
        spans.mark("fill")
    assert len(spans.snapshot()) == spans.RING_ROWS


def test_begin_end_without_with_and_a_double_end():
    t = spans.clock()
    sp = spans.span("manual").begin()
    sp.end()
    sp.end()
    assert [r[NAME] for r in rows_since(t)] == ["manual"]
    assert spans._stack() == []


def test_the_open_span_stack_is_per_thread():
    seen = {}

    def other():
        seen["open"] = list(spans._stack())
        with spans.span("other.thread"):
            pass

    t = spans.clock()
    with spans.span("main.thread"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
    assert seen["open"] == []
    by = {r[NAME]: r for r in rows_since(t)}
    assert by["other.thread"][PARENT] is None


def test_since_keeps_rows_that_ended_at_or_after_it():
    with spans.span("before"):
        pass
    t = spans.clock()
    with spans.span("after"):
        pass
    assert [r[NAME] for r in rows_since(t)] == ["after"]


# -- the older surfaces are users of it --------------------------------------

def test_record_event_lands_in_the_ring():
    t = spans.clock()
    with profiler.RecordEvent("user.block"):
        ev = profiler.RecordEvent("user.manual")
        ev.begin()
        ev.end()
        ev.end()                             # idempotent, as before
    by = {r[NAME]: r for r in rows_since(t)}
    assert by["user.manual"][PARENT] == by["user.block"][SID]


def test_step_timeline_phase_opens_through_the_primitive():
    tl = StepTimeline()
    t = spans.clock()
    tl.begin_step(7)
    with tl.phase("data_fetch"):
        time.sleep(0.002)
    tl.end_step()
    with tl.phase("checkpoint_commit"):      # a background phase
        pass
    got = [r for r in rows_since(t) if r[NAME].startswith("train.")]
    assert [(r[NAME], r[ATTRS]) for r in got] == [
        ("train.data_fetch", {"step": 7}), ("train.checkpoint_commit", {})]
    # the timeline's own row carries the span's stamps, not a second read
    phase = [s for s in tl.spans.values() if s["name"] == "data_fetch"][0]
    assert phase["t_start"] == pytest.approx(got[0][START] - tl.t0, abs=1e-9)
    assert phase["t_end"] == pytest.approx(got[0][END] - tl.t0, abs=1e-9)
    assert tl.phase_seconds["data_fetch"] == pytest.approx(
        got[0][END] - got[0][START])


def test_flight_ring_takes_a_closed_span_as_its_step_record():
    rec = FlightRecorder(capacity=4, name="fr-span")
    with spans.span("engine.step", step=3) as sp:
        sp.set(running=2)
    rec.record_span(sp)
    ev = rec.peek("x")["events"][-1]
    assert ev == {"step": 3, "running": 2, "t": round(sp.t1, 6)}
    assert rec._ring[-1] is sp.attrs         # one dict, not a copy
    rec.record(step=4)                       # the kwargs form stays
    assert rec.peek("x")["events"][-1]["step"] == 4


# -- Engine.step --------------------------------------------------------------

STEP_CHILDREN = ["engine.reap", "engine.admit", "engine.prepare_decode",
                 "engine.decode", "engine.pull", "engine.deliver"]
ADMIT_CHILDREN = ["engine.prefix_lookup", "engine.stage", "engine.prefill",
                  "engine.first_token", "engine.register"]


@pytest.fixture(scope="module")
def engine_run(serving_model):
    """A paged ``gpt_tiny`` engine driven through three requests that share
    a prefix; returns ``(engine, rows of the run, requests)``."""
    tr = RequestTracer()
    eng = Engine(serving_model, num_slots=4, max_seq=64, min_bucket=8,
                 block_size=8, tracer=tr)
    eng.warmup()
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, 100, (16,))
    t = spans.clock()
    reqs = [eng.add_request(
        np.concatenate([prefix, rng.integers(1, 100, (5 + i,))]),
        max_new_tokens=4 + i) for i in range(3)]
    eng.run()
    assert all(r.finished for r in reqs)
    return eng, rows_since(t), reqs


def kids_of(rows):
    out = {}
    for r in rows:
        out.setdefault(r[PARENT], []).append(r)
    return out


def test_every_engine_step_has_its_phases_in_order(engine_run):
    eng, rows, _reqs = engine_run
    kids = kids_of(rows)
    steps = [r for r in rows if r[NAME] == "engine.step"]
    assert len(steps) >= 4 and all(r[PARENT] is None for r in steps)
    for st in steps:
        names = [c[NAME] for c in kids[st[SID]]]
        # the order of the table in PERF.md: reap, admits, then the decode
        order = [STEP_CHILDREN.index(n) for n in names]
        assert order == sorted(order), names
        assert names[0] == "engine.reap"
        assert names[-4:] == STEP_CHILDREN[2:], names
        assert names.count("engine.admit") == st[ATTRS]["admitted"]
        assert {"step", "kv_tokens", "admitted", "running", "queued",
                "free_blocks"} <= set(st[ATTRS])
    assert [s[ATTRS]["step"] for s in steps] == list(range(
        steps[0][ATTRS]["step"], steps[0][ATTRS]["step"] + len(steps)))
    # kv_tokens is a running integer: cached tokens of the running slots
    assert steps[0][ATTRS]["kv_tokens"] == 0
    assert steps[1][ATTRS]["kv_tokens"] == sum(
        21 + i for i in range(3)) + 3        # prompts + one decoded token each
    assert steps[-1][ATTRS]["running"] == 0 and eng._kv_tokens == 0


def test_decode_chunks_on_the_step_span_is_the_kernels_work_list(
        serving_model, monkeypatch):
    """``decode_chunks`` is counted on the host from the lengths the engine
    keeps; the device's ``n`` is the work list's own, from the lengths and
    the mask the decode program is handed.  A chunk cut to 16 tokens (two
    blocks of 8; four chunks a row) makes slots cross chunk edges in a
    short run."""
    from paddle_tpu.ops.pallas import paged_attention_kernel as pk
    from paddle_tpu.ops.pallas.mla_attention_kernel import decode_work_list

    monkeypatch.setattr(pk, "DECODE_CHUNK_TOKENS", 16)
    eng = Engine(serving_model, num_slots=4, max_seq=64, min_bucket=8,
                 block_size=8)
    eng.warmup()
    ct = eng._decode_chunk_tokens
    assert ct == eng.cache.decode_chunk_tokens() == 16
    device_n, real = [], eng._step_call

    def spy(point, fn, *args, **kw):
        if point == "serving.decode":
            device_n.append(int(decode_work_list(
                eng.cache.lengths._value(), args[0]._value(), ct,
                64 // ct)[2]))
        return real(point, fn, *args, **kw)

    monkeypatch.setattr(eng, "_step_call", spy)
    rng = np.random.default_rng(1)
    t = spans.clock()
    reqs = [eng.add_request(rng.integers(1, 100, (n,)), max_new_tokens=6)
            for n in (5, 14, 30, 43)]
    eng.run()
    assert all(r.finished for r in reqs)
    steps = [r[ATTRS] for r in rows_since(t) if r[NAME] == "engine.step"]
    host_n = [a["decode_chunks"] for a in steps if "decode_chunks" in a]
    # 5 // 16 + 14 // 16 + 30 // 16 + 43 // 16 + 4 at the first decode; at
    # the third the second and the third request cross a chunk's edge
    assert host_n == device_n == [7, 7, 9, 9, 9]
    # a reference engine has no work list, and says nothing
    ref = Engine(serving_model, num_slots=2, max_seq=64, min_bucket=8,
                 block_size=8, kernel="reference")
    ref._build_steps()
    assert ref._decode_chunk_tokens is None


def test_windowed_cache_spans_carry_rows_windows_and_the_work_list():
    """A model that keeps an exact window and chunk summaries
    (``evabyte_tiny``: windows of 32 in chunks of 4): ``engine.step`` carries
    the rows its decode program counted and the windows published before it,
    ``decode_chunks`` is the windowed kernel's own work list (a summary block
    a window passed, then the chunks of the slot's place in its window),
    ``engine.prefill`` (one a window of a cold prompt) carries the windows its
    piece touched and closed, and
    ``engine.publish_window`` lies inside ``engine.prepare_decode`` around
    the publishing program."""
    from paddle_tpu.models.evabyte import EvaByteForCausalLM, evabyte_tiny
    from paddle_tpu.ops.pallas import eva_attention_kernel as eva

    paddle.seed(0)
    model = EvaByteForCausalLM(evabyte_tiny())
    model.eval()
    eng = Engine(model, num_slots=2, max_seq=128, min_bucket=8, block_size=8)
    eng.warmup(buckets=[32])
    arr = eng.cache.sides[0][0]._value()
    ct = eva.exact_chunk_tokens(arr.shape, arr.dtype.itemsize, 32)
    t = spans.clock()
    req = eng.add_request(np.random.default_rng(2).integers(1, 60, (60,)),
                          max_new_tokens=8)
    eng.run()
    assert req.finished
    rows = rows_since(t)
    kids = kids_of(rows)
    steps = [r for r in rows if r[NAME] == "engine.step"
             and "eva_context" in r[ATTRS]]
    assert len(steps) == 7
    for i, st in enumerate(steps):
        a, pos = st[ATTRS], 60 + i
        assert (a["eva_exact_rows"], a["eva_summary_rows"],
                a["eva_context"]) == (pos % 32 + 1, pos // 32 * 8, pos + 1)
        assert a["decode_chunks"] == pos // 32 + (pos % 32) // ct + 1 == int(
            eva.decode_items(np.int32(pos), window=32, chunk_tokens=ct))
        assert a["eva_windows_published"] == (pos == 64)
    # the cold 60-token prompt went in a window at a time
    fills = [r[ATTRS] for r in rows if r[NAME] == "engine.prefill"]
    assert [(a["bucket"], a["eva_windows"], a["eva_windows_published"])
            for a in fills] == [(32, 1, 1), (32, 1, 0)]
    (pub,) = [r for r in rows if r[NAME] == "engine.publish_window"]
    assert pub[ATTRS]["window"] == 1
    assert pub[ATTRS]["exact_blocks_released"] == 4
    prepare = [r for r in rows if r[NAME] == "engine.prepare_decode"
               and pub in kids.get(r[SID], [])]
    assert len(prepare) == 1
    ev = eng.stats()["eva"]
    assert (ev["windows_published_decode"], ev["windows_published_prefill"],
            ev["steps"]) == (1, 1, 7)


def test_sampler_path_on_the_step_span_is_the_way_the_program_went(
        serving_model, monkeypatch):
    """``sampler_path`` is told on the host from the running requests'
    parameters; the decode program chooses from its lanes and the ``active``
    mask it is handed.  Step by step they agree, and ``stats()["sampler"]``
    is their sum.  The slot a sampled request leaves keeps its lanes, and
    asks for nothing."""
    import jax

    from paddle_tpu.serving import SamplingParams, sampling

    ran, real_masked = [], sampling._device_masked_logits

    def spy(*args):
        jax.debug.callback(lambda: ran.append("sampled"))
        return real_masked(*args)

    monkeypatch.setattr(sampling, "_device_masked_logits", spy)
    eng = Engine(serving_model, num_slots=4, max_seq=64, min_bucket=8,
                 block_size=8)
    eng.warmup()
    assert eng.stats()["sampler"] == {"steps_greedy": 0, "steps_sampled": 0}
    device, real = [], eng._step_call

    def told(point, fn, *args, **kw):
        if point != "serving.decode":
            return real(point, fn, *args, **kw)
        del ran[:]
        out = real(point, fn, *args, **kw)
        out.numpy()                          # the step's callbacks have run
        jax.effects_barrier()
        device.append((ran or ["greedy"])[0])
        return out

    monkeypatch.setattr(eng, "_step_call", told)
    rng = np.random.default_rng(2)
    t = spans.clock()
    # a decode step less than max_new_tokens each: the first is the prefill's
    reqs = [eng.add_request(rng.integers(1, 100, (9,)), max_new_tokens=n,
                            sampling=sp) for n, sp in (
        (8, None),
        (5, SamplingParams(temperature=0.8, top_k=50, top_p=0.9, seed=1)),
        (3, SamplingParams(temperature=0.8, top_p=0.9, seed=2)))]
    eng.run()
    assert all(r.finished for r in reqs)
    steps = [r[ATTRS] for r in rows_since(t) if r[NAME] == "engine.step"]
    host = [a["sampler_path"] for a in steps if "sampler_path" in a]
    assert host == device == ["sampled"] * 4 + ["greedy"] * 3
    assert eng.stats()["sampler"] == {"steps_greedy": 3, "steps_sampled": 4}
    assert eng.flight.peek("x")["events"][-1]["sampler_path"] == "greedy"


def test_admit_spans_carry_the_request(engine_run):
    _eng, rows, reqs = engine_run
    kids = kids_of(rows)
    admits = [r for r in rows if r[NAME] == "engine.admit"]
    assert len(admits) == len(reqs)
    assert len({a[ATTRS]["trace"] for a in admits}) == len(reqs)
    for a, req in zip(admits, reqs):
        at = a[ATTRS]
        assert at["outcome"] == "admitted" and at["queue_wait_ms"] >= 0
        assert at["prompt_tokens"] == int(req.prompt_ids.size)
        assert at["trace"].endswith(f":r{req.request_id}")
        assert [c[NAME] for c in kids[a[SID]]] == ADMIT_CHILDREN
        assert kids[a[SID]][1][ATTRS] == {"programs": 1, "piece": 0}
        assert kids[a[SID]][2][ATTRS] == {"bucket": at["bucket"],
                                          "attempts": 1}
        assert at["key_passes"] == 1 and at["staging_programs"] == 1
    # the later two hit the first one's two whole prefix blocks
    assert [a[ATTRS]["hit_tokens"] for a in admits] == [0, 16, 16]


def test_step_feeds_flight_and_tracer_from_its_spans(engine_run):
    eng, rows, _reqs = engine_run
    last = [r for r in rows if r[NAME] == "engine.step"][-1]
    ev = eng.flight.peek("x")["events"][-1]
    assert ev["t"] == round(last[END], 6)
    assert {k: ev[k] for k in ("step", "running", "queued", "admitted")} == \
        {k: last[ATTRS][k] for k in ("step", "running", "queued", "admitted")}
    assert eng._last_step_t == last[END]
    # the tracer's batched decode_step event is stamped with the pull's end
    pulls = [r[END] for r in rows if r[NAME] == "engine.pull"]
    evs = [e for e in eng.tracer.events if e["kind"] == "decode_step"]
    assert [e["ts"] for e in evs[-len(pulls):]] == pytest.approx(
        [p - eng.tracer.t0 for p in pulls], abs=1e-9)


def test_a_step_costs_a_constant_number_of_rows_and_clock_reads(
        engine_run, monkeypatch):
    """No timing: a decode-only step is six spans, each two clock reads and
    one row, whatever the batch holds."""
    eng, _rows, _reqs = engine_run
    for n in (1, 3):
        for i in range(n):
            eng.add_request(np.arange(1, 9 + i), max_new_tokens=6)
        eng.step()                           # admits
        reads = []
        real = spans.clock
        monkeypatch.setattr(spans, "clock",
                            lambda: reads.append(1) or real())
        t = real()
        eng.step()                           # decode only
        monkeypatch.setattr(spans, "clock", real)
        got = rows_since(t)
        assert [r[NAME] for r in got] == [
            "engine.reap", "engine.prepare_decode", "engine.decode",
            "engine.pull", "engine.deliver", "engine.step"]
        assert len(reads) == 2 * len(got)
        eng.run()


# -- a program-cache miss ------------------------------------------------------

def test_a_miss_is_a_trace_and_a_compile_span_and_a_hit_is_nothing():
    @paddle.jit.to_static
    def doubled(x):
        return x * 2

    x = paddle.to_tensor(np.ones((3,), "float32"))
    t = spans.clock()
    doubled(x)
    doubled(x)
    got = [r for r in rows_since(t) if r[NAME].startswith("jit.")]
    assert [r[NAME] for r in got] == ["jit.trace", "jit.compile"]
    for r in got:
        assert r[ATTRS]["fn"].endswith("doubled")
        assert r[ATTRS]["arg_specs"] == "float32[3]"
        assert "test_obs_spans.py" in r[ATTRS]["site"]
        assert re.fullmatch(r"[0-9a-f]{12}", r[ATTRS]["key"])
    doubled(paddle.to_tensor(np.ones((4,), "float32")))     # another miss
    assert len([r for r in rows_since(t) if r[NAME] == "jit.trace"]) == 2


# -- device-side names ---------------------------------------------------------

@pytest.mark.parametrize("fn,want", [
    (lambda x: x, "_lambda_"),
    (np.ones((1,)).sum, "sum"),
    (type("Odd", (), {"__call__": lambda self, x: x})(), "Odd"),
])
def test_program_name_is_the_functions_own_as_an_identifier(fn, want):
    from paddle_tpu.jit.trace import program_name

    assert program_name(fn) == want


def test_program_names_leave_the_executable_cache_keys_alone():
    def first_name(x):
        return x + 1

    def second_name(x):
        return x + 1

    a, b = paddle.jit.to_static(first_name), paddle.jit.to_static(second_name)
    x = paddle.to_tensor(np.ones((2, 3), "float32"))
    a(x), b(x)
    assert list(a.program_cache) == list(b.program_cache)
    assert "jit_first_name" in a.last_program().compiled_stats()["hlo"]
    assert "jit_second_name" in b.last_program().compiled_stats()["hlo"]


def test_engine_programs_carry_their_names_and_scopes(engine_run):
    eng, _rows, _reqs = engine_run
    decode = eng._decode_fn.last_program().compiled_stats()["hlo"]
    assert decode.startswith("HloModule jit_decode_step")
    for scope in ("kv.write", "sampler.sample"):
        assert f"jit(decode_step)/{scope}" in decode or \
            re.search(rf"jit\(decode_step\)/\S*{re.escape(scope)}", decode), \
            scope
    prefill = eng._prefill_fn.last_program().compiled_stats()["hlo"]
    assert prefill.startswith("HloModule jit_prefill_step")
    assert "kv.write" in prefill and "sampler.sample" in prefill


def test_train_step_carries_its_name_and_scopes():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def tiny_train_step(x, y):
        loss = model.compute_loss(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 100, (2, 16)).astype("int64"))
    tiny_train_step(ids, ids)
    hlo = tiny_train_step.last_program().compiled_stats()["hlo"]
    assert hlo.startswith("HloModule jit_tiny_train_step")
    assert "loss.streamed_ce" in hlo                       # forward ...
    assert "transpose(jvp(loss.streamed_ce))" in hlo       # ... and backward
    assert "optimizer.adamw" in hlo
    assert "attention." in hlo


# -- the kernels' names, compiled for the chip that is described here ---------

def test_flash_kernels_are_named_and_the_accepted_patterns_still_match(
        one_chip):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ATTN_SCOPE_PALLAS
    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    def train(q, k, v):
        def loss(q, k, v):
            with jax.named_scope(ATTN_SCOPE_PALLAS):
                o = fk.flash_attention_fused(q, k, v, causal=True)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def infer(q, k, v):
        with jax.named_scope(ATTN_SCOPE_PALLAS):
            return fk.flash_attention_fused(q, k, v, causal=True)

    x = jax.ShapeDtypeStruct((2, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)
    kc = load_patterns("flash_attention")
    lines = kernel_lines(train, x, x, x)
    names = [ln.split(" = ")[0] for ln in lines]
    assert [re.sub(r"\.\d+$", "", n) for n in names] == [
        "%" + fk.FWD_NAME, "%" + fk.BWD_DKV_NAME, "%" + fk.BWD_DQ_NAME]
    fwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.FORWARD)]
    bwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.BACKWARD)]
    assert len(fwd) == 1 and len(bwd) == 2 and not set(fwd) & set(bwd)
    (only,) = kernel_lines(infer, x, x, x)
    assert any(re.search(p, only) for p in kc.FORWARD)
    assert not any(re.search(p, only) for p in kc.BACKWARD)


def test_a_traced_flash_call_leaves_one_plan_mark():
    """``attention.flash_plan``: once per trace of the forward, the sizes
    the plan chose for the call's shape and the tiles its walk visits — the
    engagement share of the causal skip (100 % visited: it did nothing)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    def train(q, k, v):
        def loss(q, k, v):
            o = fk.flash_attention_fused(q, k, v, causal=True, interpret=True)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = jax.ShapeDtypeStruct((1, 1024, 16, 64), jnp.bfloat16)  # the cell's
    t = spans.clock()
    with spans.span("jit.trace", fn="train") as outer:
        jax.eval_shape(train, x, x, x)
    marks = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert len(marks) == 1
    (mark,) = marks
    assert mark[PARENT] == outer.sid and mark[START] == mark[END]
    plan = fk.flash_plan(1024, 64, 2)
    assert mark[ATTRS] == {
        "seq": 1024, "head_dim": 64, "causal": 1, "block_q": plan.block_q,
        "sub": plan.sub, "tiles_total": plan.tiles_total,
        "tiles_visited": plan.tiles_visited,
        "tiles_masked": plan.tiles_masked,
        # the operands' form: [head_dim, S] heads, here of three tensors
        "layout": "feature_major", "fused_qkv": 0}
    assert mark[ATTRS]["tiles_visited"] <= 0.75 * mark[ATTRS]["tiles_total"]
    # a call that is not causal walks every tile and says so
    t = spans.clock()
    jax.eval_shape(lambda q, k, v: fk.flash_attention_fused(
        q, k, v, causal=False, interpret=True), x, x, x)
    (mark,) = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert mark[ATTRS]["tiles_visited"] == mark[ATTRS]["tiles_total"]
    assert mark[ATTRS]["tiles_masked"] == 0 and mark[ATTRS]["causal"] == 0
    # a caller that holds a fused projection says so, and nothing else moves
    qkv = jax.ShapeDtypeStruct((1, 1024, 16, 192), jnp.bfloat16)
    t = spans.clock()
    jax.eval_shape(lambda x: jax.grad(lambda x: fk.flash_attention_fused_qkv(
        x, causal=True, interpret=True).astype(jnp.float32).sum())(x), qkv)
    (fused,) = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert fused[ATTRS] == {**marks[0][ATTRS], "fused_qkv": 1}


def test_no_xla_op_stands_between_the_projections_and_the_flash_kernels(
        one_chip, monkeypatch):
    """One layer's attention at the train cell's call (GPT-2 345M's widths,
    B 16, S 1,024, bf16; :func:`attention_layer_program`), forward and
    backward, compiled for the described v5e: the kernels take heads as
    ``[head_dim, S]`` blocks of the qkv projection's output as XLA:TPU lays
    it out and write the out projection's input and the qkv gradient the
    same way, so no ``copy`` / ``transpose`` / fusion of a head tensor's
    size stands between a projection's matmul and a kernel (one joining
    dq, dk, dv would be allowed: there is none, ``bwd_dq`` completes
    ``bwd_dkv``'s array in place), lse is a lane-dense row a head and delta
    never leaves the kernels."""
    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    compiled = attention_layer_program(one_chip, monkeypatch)
    hlo = compiled.as_text()
    head = 16 * 16 * 1024 * 64
    lines = custom_call_lines(compiled)
    kc = load_patterns("flash_attention")
    fwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.FORWARD)]
    bwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.BACKWARD)]
    # the yardstick's reader halves the backward count: two kernels a layer
    assert len(lines) == 3 and len(fwd) == 1 and len(bwd) == 2
    assert [re.sub(r"\.\d+$", "", ln.split(" = ")[0]) for ln in lines] == [
        "%" + fk.FWD_NAME, "%" + fk.BWD_DKV_NAME, "%" + fk.BWD_DQ_NAME]
    moves = moves_around_kernels(hlo, lambda n: "pallas_flash" in n, head)
    assert moves == []
    # q, k and v are one operand, read three times; dq, dk and dv one result
    operands = re.findall(r"(\w+\[[\d,]*\])\S* (%[\w.-]+)",
                          fwd[0].split("custom-call(")[1].split(
                              "), custom_call_target")[0])
    assert len(operands) == 3 and len(set(operands)) == 1
    assert operands[0][0] == "bf16[16,3072,1024]"
    assert all(ln.split(" = ")[1].startswith("bf16[16,3072,1024]")
               for ln in bwd)
    assert 'output_to_operand_aliasing={{}: (6, {})}' in next(
        ln for ln in hlo.splitlines() if fk.BWD_DQ_NAME + "." in ln
        and "tpu_custom_call" in ln)
    # no [.., S, 1] float32 array: lse is [B*H, 1, S], delta stays in VMEM
    assert not re.search(r"f32\[[\d,]*1024,1\]", hlo)
    assert "f32[256,1,1024]" in fwd[0]


def test_paged_kernels_are_named_and_decode_is_still_told_by_its_operands(
        one_chip):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention_kernel as pk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, heads, hd, bs, blocks, mb = 32, 16, 64, 16, 2049, 64
    pool = sds((blocks, bs, heads, 128), jnp.bfloat16)   # hd in whole lanes
    kc = load_patterns("paged_decode")
    (decode,) = kernel_lines(
        lambda q, k, v, t, n, a: pk.paged_decode_attention_kernel(
            q, k, v, t, n, a),
        sds((slots, 1, heads, hd), jnp.bfloat16), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32))
    assert decode.startswith("%paged_decode_attention.")
    assert any(re.search(p, decode) for p in kc.PATTERNS)
    # the table and the lengths lead: a dynamic grid bound would come first
    assert re.match(r"%\S+ = \S+ custom-call\(s32\[32,64\]\S* %\S+ "
                    r"s32\[32\]\S* %\S+ ", decode)
    (prefill,) = kernel_lines(
        lambda q, k, v, row, st: pk.paged_prefill_attention_kernel(
            q, k, v, row, st),
        sds((1, 256, heads, hd), jnp.bfloat16), pool, pool,
        sds((mb,), jnp.int32), sds((), jnp.int32))
    assert prefill.startswith("%paged_prefill_attention.")
    assert not any(re.search(p, prefill) for p in kc.PATTERNS)
    # in the engine's own decode program: one match a layer, nothing else
    _eng, compiled = engine_program(one_chip, "decode", layers=2)
    told = [ln for ln in custom_call_lines(compiled)
            if any(re.search(p, ln) for p in kc.PATTERNS)]
    assert len(told) == 2
    assert all(ln.startswith("%paged_decode_attention.") for ln in told)


# -- the engine's programs, compiled for the chip that is described here ------

@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_programs_move_no_layer_buffer_of_the_pool_on_the_chip(
        one_chip, program):
    """The paged engine's decode and bucket-32 prefill programs at GPT-2
    345M's widths (:func:`engine_program`, one layer): XLA:TPU stores a
    buffer whose minor dim is 64 with the block dim minor-most and converts
    it to the Pallas kernel's row-major, lane-padded operand and back in
    every program; the pool's per-layer buffers in whole lanes are the
    operand, written in place."""
    import chip_smoke

    eng, compiled = engine_program(one_chip, program)
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    pools, layer_buf = eng.cache.nbytes(), eng.cache.layer_nbytes()
    assert layer_buf == 513 * 16 * 16 * 128 * 2
    assert hlo.count(chip_smoke.PALLAS_CALL) == 1
    assert chip_smoke.pool_sized_moves(hlo, layer_buf) == []
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < layer_buf


def vocab_sorts(hlo: str, vocab: int) -> list:
    """The ``sort`` instructions of an optimized HLO module that order rows
    ``vocab`` wide, and the selections (``TopK``) the chip sorts them for."""
    return [ln.strip()[:120] for ln in hlo.splitlines()
            if re.search(rf"\[(\d+,)*{vocab}\]\S*\)? sort\(", ln)
            or 'custom_call_target="TopK"' in ln]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_programs_do_not_sort_the_vocabulary(one_chip, program):
    """The sampler searches its two cut-offs in fused passes over
    ``[slots, V]``; until PR 29 every decode step sorted those rows (half
    of the chat cell's device step).  A program that sorts them is told."""
    import jax
    import jax.numpy as jnp

    plain = jax.jit(lambda z: jnp.sort(z, axis=-1)).lower(
        jax.ShapeDtypeStruct((8, 4096), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    assert len(vocab_sorts(plain, 4096)) == 1
    _eng, compiled = engine_program(one_chip, program)
    assert vocab_sorts(compiled.as_text(), 50304) == []


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_pool_programs_move_no_layer_buffer_on_the_chip(
        one_chip, program):
    """The same proof for the pool of a latent-attention model:
    JoyAI-LLM-Flash's widths (latent 512 + 64 stored in 640 lanes, 32 heads,
    experts of 768 with 32 of 256 held; one dense and one expert layer,
    vocabulary cut to 8,192), 32 slots, block 16, a 2,049-block pool of ONE
    buffer a layer ``[2049, 16, 1, 640]``: the decode program and the
    bucket-256 prefill program, with ``mla_paged_decode`` /
    ``mla_flash_prefill`` + ``mla_paged_prefill`` (the tail over itself, and
    over the cached prefix behind a ``start > 0``) and ``moe_grouped_matmul``
    as the chip runs them, hold no ``copy`` / ``transpose`` / ``slice`` of a
    layer buffer's size and alias the whole pool."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import deepseek_v3 as dm
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = dm.DeepseekV3ForCausalLM(dm.DeepseekV3Config(
        vocab_size=8192, num_hidden_layers=2, held_experts=(0, 32),
        max_position_embeddings=1024, dtype="bfloat16"))
    eng = Engine(model, num_slots=32, max_seq=1024, min_bucket=256,
                 block_size=16, kernel="pallas")
    assert [tuple(b.shape) for b in eng.cache.buffers()] == \
        [(2049, 16, 1, 640)] * 2
    eng.cache._interpret = False          # the kernels as the chip runs them
    interpret, dm._interpret = dm._interpret, lambda: False
    try:
        eng._build_steps()
        if program == "decode":
            fn, args = eng._decode_fn, [np.zeros((32,), np.int32)]
        else:
            fn, args = eng._prefill_fn, [np.zeros((1, 256), np.int64),
                                         np.int32(0), np.int32(1), np.int32(0)]
            assert eng.cache.begin_sequence(0, [], 0, 256)
        leaves = []
        args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
        prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

        def on_chip(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        with no_grad():
            prog.build(leaves)
            sd, sk = prog._split_state([k.current() for k in prog.state_keys])
            compiled = prog.jitted_donate.lower(
                [on_chip(t._value()) for t in leaves],
                [on_chip(a) for a in sd], [on_chip(a) for a in sk]).compile()
    finally:
        dm._interpret = interpret
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    pool, layer_buf = eng.cache.nbytes(), eng.cache.layer_nbytes()
    assert layer_buf == 2049 * 16 * 640 * 2 and pool == 2 * layer_buf
    # one attention call a layer (two in a prefill: the flash pass, and the
    # absorbed kernel in the branch a cached prefix takes), two grouped
    # products in the expert layer
    attention = {"decode": ["mla_paged_decode"],
                 "prefill": ["mla_flash_prefill", "mla_paged_prefill"]}
    assert hlo.count(chip_smoke.PALLAS_CALL) == 2 * len(attention[program]) + 2
    for kernel in (*attention[program], "moe_grouped_matmul"):
        assert re.search(r"%" + kernel + r"(\.\d+)? = ", hlo), kernel
    assert chip_smoke.pool_sized_moves(hlo, layer_buf) == []
    assert mem.alias_size_in_bytes >= pool


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_indexed_pool_programs_move_no_layer_buffer_on_the_chip(
        one_chip, program):
    """The same proof for the three-sided pool of a model whose attention
    runs under an indexer: Keye-VL-2.0-30B-A3B's widths (32 query / 4 KV
    heads of 128, indexer 16 x 64 with ``topk`` 2,048, experts of 768 with
    16 of 128 held; two layers, vocabulary cut to 8,192), 16 slots of 8,192
    positions, block 16, an 8,193-block pool of THREE buffers a layer (K and
    V ``[8193, 16, 4, 128]``, the indexer's key ``[8193, 16, 1, 128]``): the
    decode program and the bucket-512 prefill program, with the dense and the
    indexed branch each (``paged_*_attention`` beside ``dsa_index_scores`` +
    ``dsa_sparse_decode`` / ``dsa_sparse_prefill``) and ``kv_block_write`` as
    the chip runs them, hold no ``copy`` / ``transpose`` / ``slice`` of a
    layer buffer's size (four KV heads of bfloat16 do not fill a sublane
    tile: an XLA scatter of a tail's blocks converts the whole buffer there
    and back), alias the whole pool, sort no context-long row, and hold no
    float32 array of the bucket by the context."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import keye_vl2 as km
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = km.KeyeVL2ForCausalLM(km.KeyeVL2Config(
        vocab_size=8192, num_hidden_layers=2, held_experts=(0, 16),
        max_position_embeddings=8192, dtype="bfloat16"))
    eng = Engine(model, num_slots=16, max_seq=8192, min_bucket=512,
                 block_size=16, kernel="pallas")
    assert [tuple(b.shape) for b in eng.cache.buffers()] == \
        [(8193, 16, 4, 128)] * 4 + [(8193, 16, 1, 128)] * 2
    eng.cache._interpret = False          # the kernels as the chip runs them
    interpret, km._interpret = km._interpret, lambda: False
    try:
        eng._build_steps()
        if program == "decode":
            fn, args = eng._decode_fn, [np.zeros((16,), np.int32)]
        else:
            fn, args = eng._prefill_fn, [np.zeros((1, 512), np.int64),
                                         np.int32(0), np.int32(1), np.int32(0)]
            assert eng.cache.begin_sequence(0, [], 0, 512)
        leaves = []
        args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
        prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

        def on_chip(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        with no_grad():
            prog.build(leaves)
            sd, sk = prog._split_state([k.current() for k in prog.state_keys])
            compiled = prog.jitted_donate.lower(
                [on_chip(t._value()) for t in leaves],
                [on_chip(a) for a in sd], [on_chip(a) for a in sk]).compile()
    finally:
        km._interpret = interpret
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    pool = eng.cache.nbytes()
    index_buf = int(eng.cache.sides[2][0]._value().nbytes)
    assert eng.cache.layer_nbytes() == 8193 * 16 * 4 * 128 * 2
    assert index_buf == 8193 * 16 * 128 * 2
    kernels = {"decode": ("paged_decode_attention", "dsa_index_scores",
                          "dsa_sparse_decode"),
               "prefill": ("paged_prefill_attention", "dsa_index_scores",
                           "dsa_sparse_prefill", "kv_block_write")}[program]
    for kernel in kernels + ("moe_grouped_matmul",):
        assert re.search(r"%" + kernel + r"(\.\d+)? = ", hlo), kernel
    assert chip_smoke.pool_sized_moves(hlo, index_buf) == []
    assert mem.alias_size_in_bytes >= pool
    assert mem.temp_size_in_bytes < eng.cache.layer_nbytes()
    assert vocab_sorts(hlo, 8192) == []       # the context's length, too
    assert not re.search(r"f32\[(\d+,)*512,8192\]", hlo) or program == "prefill"
    assert not re.search(r"f32\[(\d+,)*8192,8192\]", hlo)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_grouped_query_heads_of_128_compile_for_the_chip(one_chip, program):
    """ROADMAP R-a, repaired in PR 30: a rotary decoder of 32 query / 8 KV
    heads x 128 in bfloat16 through the paged engine.  Until then the rotary
    returned float32 queries (``q * cos`` promotes) and the first prefill
    bucket died in ``warmup()`` with ``RESOURCE_EXHAUSTED ... vmem ... 17.09M
    and limit 16.00M``; and the prefill program's block scatter converted
    each K/V layer buffer to a layout of XLA's own and back (8 heads of
    bfloat16 do not fill a sublane tile).  Both programs compile for the
    described v5e, the bucket-1024 prefill within the kernel's VMEM, and
    move no layer buffer."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=8192, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=1, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=4096))
    model.to(dtype="bfloat16")
    eng = Engine(model, num_slots=8, max_seq=4096, min_bucket=512,
                 block_size=16, kernel="pallas")
    assert [tuple(b.shape) for b in eng.cache.buffers()] == \
        [(2049, 16, 8, 128)] * 2
    eng.cache._interpret = False          # the kernels as the chip runs them
    eng._build_steps()
    if program == "decode":
        fn, args = eng._decode_fn, [np.zeros((8,), np.int32)]
    else:
        fn, args = eng._prefill_fn, [np.zeros((1, 1024), np.int64),
                                     np.int32(0), np.int32(1), np.int32(0)]
        assert eng.cache.begin_sequence(0, [], 0, 1024)
    leaves = []
    args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
    prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with no_grad():
        prog.build(leaves)
        sd, sk = prog._split_state([k.current() for k in prog.state_keys])
        compiled = prog.jitted_donate.lower(
            [on_chip(t._value()) for t in leaves], [on_chip(a) for a in sd],
            [on_chip(a) for a in sk]).compile()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert re.search(r"%paged_" + program + r"_attention(\.\d+)? = ", hlo)
    assert (re.search(r"%kv_block_write(\.\d+)? = ", hlo) is not None) \
        == (program == "prefill")
    assert chip_smoke.pool_sized_moves(hlo, eng.cache.layer_nbytes()) == []
    assert mem.alias_size_in_bytes >= eng.cache.nbytes()


@pytest.mark.parametrize("program", ["decode", "prefill", "publish"])
def test_windowed_pool_programs_move_no_layer_buffer_on_the_chip(
        one_chip, program):
    """The same proof for the two-group pool of a model that keeps an exact
    window and chunk summaries: EvaByte's widths (32 heads of 128 with as
    many KV heads, SwiGLU of 11,008, windows of 2,048 in chunks of 16,
    vocabulary 320; two layers), 16 slots of 32,768 positions, block 16, a
    641-block exact group (K and V ``[641, 16, 32, 128]`` a layer) and a
    33-block summary group (``[33, 128, 32, 128]``): the decode program
    (``eva_paged_decode`` and no other attention kernel: no fork a layer),
    the bucket-512 prefill program (the tail's write, the window it may
    close published, ``eva_paged_prefill``) and the publishing program, as
    the chip runs them, hold no ``copy`` / ``transpose`` / ``slice`` of a
    layer buffer's size of either group — nor of a projection's weights,
    which stored input-major were copied in every program — and alias every
    buffer they write: the exact group in decode, both in prefill, the
    summary group in the publishing program."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import evabyte as em
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = em.EvaByteForCausalLM(em.EvaByteConfig(num_hidden_layers=2,
                                                   dtype="bfloat16"))
    eng = Engine(model, num_slots=16, max_seq=32768, min_bucket=512,
                 block_size=16, num_kv_blocks=641, num_summary_blocks=33,
                 kernel="pallas")
    assert [tuple(b.shape) for b in eng.cache.buffers()] == \
        [(641, 16, 32, 128)] * 4
    assert [tuple(b.shape) for b in eng.cache.summary_buffers()] == \
        [(33, 128, 32, 128)] * 4
    eng.cache._interpret = False          # the kernels as the chip runs them
    eng._build_steps()
    if program == "decode":
        fn, args = eng._decode_fn, [np.zeros((16,), np.int32)]
    elif program == "publish":
        fn, args = eng._publish_fn, [np.int32(0), np.int32(0)]
    else:
        fn, args = eng._prefill_fn, [np.zeros((1, 512), np.int64),
                                     np.int32(0), np.int32(1), np.int32(0)]
        assert eng.cache.begin_sequence(0, None, 0, 512)
    leaves = []
    args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
    prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with no_grad():
        prog.build(leaves)
        sd, sk = prog._split_state([k.current() for k in prog.state_keys])
        compiled = prog.jitted_donate.lower(
            [on_chip(t._value()) for t in leaves], [on_chip(a) for a in sd],
            [on_chip(a) for a in sk]).compile()
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    exact = sum(int(b._value().nbytes) for b in eng.cache.buffers())
    summary = sum(int(b._value().nbytes)
                  for b in eng.cache.summary_buffers())
    assert eng.cache.nbytes() == exact + summary
    assert eng.cache.layer_nbytes() == 641 * 16 * 32 * 128 * 2
    summary_buf = 33 * 128 * 32 * 128 * 2
    kernels = {"decode": ("eva_paged_decode",),
               "prefill": ("eva_paged_prefill",), "publish": ()}[program]
    for kernel in kernels:
        assert re.search(r"%" + kernel + r"(\.\d+)? = ", hlo), kernel
    assert not re.search(r"%paged_decode_attention(\.\d+)? = ", hlo)
    # a projection's weights are 4096 x 4096 x 2 B = 32 MiB, under both
    assert chip_smoke.pool_sized_moves(hlo, 4096 * 4096 * 2) == []
    written = {"decode": exact, "prefill": exact + summary,
               "publish": summary}[program]
    assert mem.alias_size_in_bytes >= written
    assert mem.temp_size_in_bytes < eng.cache.layer_nbytes()
    assert summary_buf < eng.cache.layer_nbytes()
    # the scopes of the two halves, in the ops' names
    scopes = {"decode": ("eva.attend", "kv.write"),
              "prefill": ("eva.attend", "eva.summarise", "kv.write"),
              "publish": ("eva.summarise",)}[program]
    for scope in scopes:
        assert scope in hlo, scope


@pytest.mark.parametrize("hkv,rep,hd,bucket,mb,window,indexed", [
    (4, 8, 128, 256, 2048, 0, False),     # Mellum2's full layers: the ide tail
    (4, 8, 128, 2048, 2048, 1024, False),  # its window layers: a resident piece
    (4, 8, 128, 2048, 2048, 0, False),
    (16, 1, 64, 32, 64, 0, False),        # GPT-2 345M: 64 wide in 128 lanes
    (16, 1, 64, 1024, 64, 0, False),
    (32, 1, 128, 256, 2048, 0, False),    # 32 KV heads of 128: ROADMAP R8's
    (32, 1, 128, 2048, 2048, 0, False),
    (8, 4, 64, 256, 1024, 0, False),      # LFM2-24B-A2B: the agent's tail
    (8, 4, 64, 2048, 1024, 0, False),
    (4, 8, 128, 512, 2048, 0, True),      # Keye-VL-2.0: the long-document
                                          # question's 512 queries at once
])
def test_tail_prefill_kernel_fits_scoped_vmem_at_the_cells_widths(
        one_chip, hkv, rep, hd, bucket, mb, window, indexed):
    """The tail-prefill kernel alone, its tile and chunk from
    :func:`prefill_plan`, compiles for the described v5e inside the default
    16 MiB of scoped VMEM (it asks for no more) with its two chunk buffers a
    side at the widths the cells run it at — and at 32 KV heads of 128, where
    the kernel it replaced wanted 49.9 MB (its 16-key score tiles padded to
    128 lanes).  ``indexed``: the same kernel under the indexed model's
    selection (a 64-token tile of the scores beside the queries), as
    ``sparse_prefill`` calls it for the 512 queries scored at once."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import dsa_attention_kernel as dsa
    from paddle_tpu.ops.pallas import paged_attention_kernel as pk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((mb + 1, 16, hkv, 128), jnp.bfloat16)
    if indexed:
        S = min(bucket, dsa.PREFILL_SCORE_ROWS)
        assert dsa.sparse_prefill_plan(S, 16, mb) == (64, 256)
        (line,) = kernel_lines(
            lambda q, k, v, row, st, ln, sc, cut: dsa.sparse_prefill(
                q, k, v, row, st, ln, sc, cut, scale=hd ** -0.5),
            sds((S, hkv * rep, hd), jnp.bfloat16), pool, pool,
            sds((mb,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32),
            sds((S, mb * 16), jnp.float32), sds((S,), jnp.float32))
        assert line.startswith("%dsa_sparse_prefill.")
        return
    ts, ct = pk.prefill_plan(bucket, hkv, rep, 128, 2, 16, mb)
    assert hkv * rep * ts * ct * 4 <= pk.PREFILL_SCORE_BYTES
    assert ct % 16 == 0 and bucket % ts == 0 and ts >= 16
    (line,) = kernel_lines(
        lambda q, k, v, row, st, ln: pk.paged_prefill_attention_kernel(
            q, k, v, row, st, ln, window=window),
        sds((1, bucket, hkv * rep, hd), jnp.bfloat16), pool, pool,
        sds((mb,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32))
    assert line.startswith("%paged_prefill_attention.")


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill-2048"])
def test_by_layer_pool_programs_move_no_layer_buffer_on_the_chip(
        one_chip, program):
    """The same proof for a cache stated by layer: Mellum2-12B-A2.5B's widths
    (32 query / 4 KV heads of 128, hidden 2304, experts of 896 with 8 of 64
    held, a window of 1,024; three sliding layers and one full layer,
    vocabulary cut to 8,192), 32 slots of 32,768 positions, block 16, a
    2,305-block full group (K and V ``[2305, 16, 4, 128]`` for ONE layer) and
    a 385-block window group (``[385, 16, 4, 128]`` for three): the decode
    program — one program for both kinds of layer, four calls of the one
    decode kernel — and the bucket-256 and bucket-2,048 prefill programs
    (``kv_block_write`` and ``paged_prefill_attention`` a layer, under a
    window on three of them), as the chip runs them, hold no ``copy`` /
    ``transpose`` / ``slice`` of a layer buffer's size of either group nor
    of a projection's weights, and alias both groups whole."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import keye_vl2 as km
    from paddle_tpu.models import mellum as mm
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = mm.MellumForCausalLM(mm.MellumConfig(
        vocab_size=8192, num_hidden_layers=4, held_experts=(0, 8),
        max_position_embeddings=32768, dtype="bfloat16"))
    eng = Engine(model, num_slots=32, max_seq=32768, min_bucket=256,
                 block_size=16, num_kv_blocks=2305, num_window_blocks=385,
                 kernel="pallas")
    full, win = eng.cache.pools
    assert [tuple(b.shape) for b in full.buffers()] == [(2305, 16, 4, 128)] * 2
    assert [tuple(b.shape) for b in win.buffers()] == [(385, 16, 4, 128)] * 6
    assert eng.buckets == [256, 512, 1024, 2048]       # two windows at most
    for p in eng.cache.pools:
        p._interpret = False              # the kernels as the chip runs them
    interpret, km._interpret = km._interpret, lambda: False
    try:
        eng._build_steps()
        if program == "decode":
            fn, args = eng._decode_fn, [np.zeros((32,), np.int32)]
        else:
            bucket = 2048 if program.endswith("2048") else 256
            fn, args = eng._prefill_fn, [np.zeros((1, bucket), np.int64),
                                         np.int32(0), np.int32(1), np.int32(0)]
            assert eng.cache.begin_sequence(0, None, 0, bucket)
        leaves = []
        args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
        prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

        def on_chip(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        with no_grad():
            prog.build(leaves)
            sd, sk = prog._split_state([k.current() for k in prog.state_keys])
            compiled = prog.jitted_donate.lower(
                [on_chip(t._value()) for t in leaves],
                [on_chip(a) for a in sd], [on_chip(a) for a in sk]).compile()
    finally:
        km._interpret = interpret
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    assert eng.cache.nbytes() == (2305 * 2 + 385 * 6) * 16 * 4 * 128 * 2
    kernels = {"decode": ("paged_decode_attention",)}.get(
        program, ("paged_prefill_attention", "kv_block_write"))
    for kernel in kernels + ("moe_grouped_matmul",):
        assert len(re.findall(r"%" + kernel + r"(\.\d+)? = ", hlo)) >= 4, kernel
    # nothing of either group's buffers' shape moves, nor of a q
    # projection's weights' (the expert layer's rows of a 2,048 bucket,
    # ``[16384, 2304]``, are larger than both and are its own to order)
    moved = [m for m in chip_smoke.pool_sized_moves(hlo, win.layer_nbytes())
             if any(shape in m for shape in (
                 "[2305,16,4,128]", "[385,16,4,128]", "[4096,2304]",
                 "[2304,4096]"))]
    assert moved == []
    assert mem.alias_size_in_bytes >= eng.cache.nbytes()
    # the 2,048 bucket's expert rows ([16384, 2304] and [16384, 1792]) are
    # larger than a layer buffer; no copy of a pool is among the temporaries
    assert mem.temp_size_in_bytes < full.layer_nbytes() * (
        8 if program.endswith("2048") else 1)
    assert "kv.write" in hlo and "moe.experts" in hlo
    # each layer's attention kernel is its layer's own device time (PR 36's
    # map): one call a layer, whatever the layer's kind
    from paddle_tpu.obs import hlo_cost

    got = hlo_cost.scope_map(hlo)["instructions"]
    kernel = kernels[0]
    calls = re.findall(r"%(" + kernel + r"(?:\.\d+)?) = ", hlo)
    assert sorted(got[n] for n in calls) == [
        (f"MellumForCausalLM/model/layers/{i}/self_attn/{kernel}", "fwd")
        for i in range(4)]


@pytest.mark.parametrize("program", ["decode", "prefill", "prefill-2048"])
def test_state_pool_programs_move_no_buffer_on_the_chip(one_chip, program):
    """The same proof for a cache with a state group: LFM2-24B-A2B's widths
    (hidden 2048, 32 query / 8 KV heads of 64, a 3-tap filter, dense SwiGLU
    of 11,776, experts of 1,536 with 8 of 64 held; conv, conv, attention,
    conv: two dense and two expert layers, vocabulary cut to 8,192), 64
    slots of 16,384 positions, block 16, a 2,305-block K/V group (``[2305,
    16, 8, 128]`` for ONE layer, 64-wide heads in 128 lanes), a state array
    ``[3, 2, 64, 2048]`` and a snapshot pool ``[3, 2, 4096, 2048]``: the
    decode program and the bucket-256 and bucket-2,048 prefill programs, as
    the chip runs them, hold no ``copy`` / ``transpose`` / ``slice`` of a
    layer buffer's size, of the state array's or of the snapshot pool's, and
    alias all three whole; the decode kernel at (8 KV heads, 4 query heads
    each, 64 in 128 lanes) fits the default scoped VMEM (the compile asks for
    no more); the conv operator's work lies under its layer's scope."""
    import jax

    import chip_smoke
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import held_experts as he
    from paddle_tpu.models import lfm2 as lm
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = lm.Lfm2ForCausalLM(lm.Lfm2Config(
        vocab_size=8192, num_hidden_layers=4, held_experts=(0, 8),
        max_position_embeddings=16384, dtype="bfloat16"))
    eng = Engine(model, num_slots=64, max_seq=16384, min_bucket=256,
                 block_size=16, num_kv_blocks=2305, num_state_snapshots=4096,
                 kernel="pallas")
    (kv,), (state,) = eng.cache.pools, eng.cache.states
    assert [tuple(b.shape) for b in kv.buffers()] == [(2305, 16, 8, 128)] * 2
    assert tuple(state.state.shape) == (3, 2, 64, 2048)
    assert tuple(state.snapshots.shape) == (3, 2, 4096, 2048)
    assert eng.buckets == [256, 512, 1024, 2048, 4096, 8192, 16384]
    kv._interpret = False                 # the kernels as the chip runs them
    interpret, he._interpret = he._interpret, lambda: False
    lm_interpret, lm._interpret = lm._interpret, lambda: False
    try:
        eng._build_steps()
        if program == "decode":
            fn, args = eng._decode_fn, [np.zeros((64,), np.int32)]
        else:
            bucket = 2048 if program.endswith("2048") else 256
            fn, args = eng._prefill_fn, [np.zeros((1, bucket), np.int64),
                                         np.int32(0), np.int32(1), np.int32(0)]
            assert eng.cache.begin_sequence(0, None, 0, bucket)
        leaves = []
        args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
        prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

        def on_chip(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

        with no_grad():
            prog.build(leaves)
            sd, sk = prog._split_state([k.current() for k in prog.state_keys])
            compiled = prog.jitted_donate.lower(
                [on_chip(t._value()) for t in leaves],
                [on_chip(a) for a in sd], [on_chip(a) for a in sk]).compile()
    finally:
        he._interpret, lm._interpret = interpret, lm_interpret
    hlo, mem = compiled.as_text(), compiled.memory_analysis()
    state_bytes = 3 * 2 * 64 * 2048 * 2
    assert eng.cache.nbytes() == 2305 * 2 * 16 * 8 * 128 * 2 \
        + state_bytes * (1 + 64)
    kernels = {"decode": ("paged_decode_attention",)}.get(
        program, ("paged_prefill_attention", "kv_block_write"))
    for kernel in kernels:                # one attention layer (K and V)
        assert 1 <= len(re.findall(r"%" + kernel + r"(\.\d+)? = ", hlo)) <= 2
    assert len(re.findall(r"%moe_grouped_matmul(\.\d+)? = ", hlo)) == 4
    # nothing of the state array's size or more moves that has a pool's, the
    # state's or the snapshot pool's shape (a 2,048 bucket's own rows are
    # larger and are the program's to order)
    moved = [m for m in chip_smoke.pool_sized_moves(hlo, state_bytes)
             if any(shape in m for shape in (
                 "[2305,16,8,128]", "[3,2,64,2048]", "[3,2,4096,2048]",
                 "[2,64,2048]", "[2,4096,2048]"))]
    assert moved == []
    # (a decode step takes no snapshot: the pool is no operand of it)
    assert mem.alias_size_in_bytes >= eng.cache.nbytes() - (
        state.snapshots._value().nbytes if program == "decode" else 0)
    assert ("[3,2,4096,2048]" in hlo) == (program != "decode")
    # (the 2,048 bucket's dense rows, ``[2048, 23552]`` float32, are larger
    # than the snapshot pool; no copy of a pool is among the temporaries)
    assert mem.temp_size_in_bytes < state.snapshots._value().nbytes * (
        3 if program.endswith("2048") else 1)
    assert "kv.write" in hlo and "state.write" in hlo and "conv.mix" in hlo
    from paddle_tpu.obs import hlo_cost

    got = hlo_cost.scope_map(hlo)["instructions"]
    scopes = {s for s, _d in got.values()}
    root = "Lfm2ForCausalLM/model/layers"
    for i in (0, 1, 3):
        for part in ("in_proj", "out_proj", "conv.mix", "state.write"):
            assert any(s.startswith(f"{root}/{i}/conv/{part}")
                       for s in scopes), (i, part)
    (call,) = re.findall(r"%(" + kernels[0] + r"(?:\.\d+)?) = ", hlo)
    assert got[call] == (f"{root}/2/self_attn/{kernels[0]}", "fwd")

"""The program's one span primitive (``paddle_tpu.obs.spans``), the surfaces
that go through it, the spans of a program-cache miss, the names a compiled
program carries, and every family's engine programs (and the tail-prefill
kernel at the cells' widths) compiled for the chip that is described here.  The spans of ``Engine.step`` are in
``test_obs_engine_spans.py`` and the kernels' names in
``test_obs_kernel_names.py``: the three share nothing expensive and spread
over three workers."""
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

from chip_programs import kernel_lines, one_chip, pool_programs  # noqa: F401,E501
from families import BY_KIND

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


# -- the engine's programs, compiled for the chip that is described here ------

def vocab_sorts(hlo: str, vocab: int) -> list:
    """The ``sort`` instructions of an optimized HLO module that order rows
    ``vocab`` wide, and the selections (``TopK``) the chip sorts them for."""
    return [ln.strip()[:120] for ln in hlo.splitlines()
            if re.search(rf"\[(\d+,)*{vocab}\]\S*\)? sort\(", ln)
            or 'custom_call_target="TopK"' in ln]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_engine_programs_do_not_sort_the_vocabulary(one_chip, pool_programs,
                                                    program):
    """The sampler searches its two cut-offs in fused passes over
    ``[slots, V]``; until PR 29 every decode step sorted those rows (half
    of the chat cell's device step).  A program that sorts them is told."""
    import jax
    import jax.numpy as jnp

    plain = jax.jit(lambda z: jnp.sort(z, axis=-1)).lower(
        jax.ShapeDtypeStruct((8, 4096), jnp.float32,
                             sharding=one_chip)).compile().as_text()
    assert len(vocab_sorts(plain, 4096)) == 1
    _eng, compiled = pool_programs("paged", program)
    assert vocab_sorts(compiled.as_text(), 50304) == []


@pytest.mark.parametrize("kind,program", [
    pytest.param(kind, program, id=f"{kind}-{program}")
    for kind, f in BY_KIND.items() for program in f.chip["programs"]])
def test_pool_programs_move_no_layer_buffer_on_the_chip(pool_programs, kind,
                                                        program):
    """Every kind of cache a family states, at the widths of a cell that
    serves it (``families.py``: each family's ``chip``), through the paged
    engine: its decode program, its prefill program at the buckets named and
    what other program it has, as the chip runs them, hold the buffers the
    entry lists, call the kernels it names (and not those it rules out), hold
    no ``copy`` / ``transpose`` / ``slice`` of the size of the cache's
    smallest buffer or more (of the shapes named, where the entry names
    some: a long bucket's own rows are larger and are the program's to
    order), alias every buffer they write (the whole cache, unless the entry
    says what a program leaves alone), keep their temporaries under the
    entry's bound, carry the scopes it names and none of the arrays it
    forbids; where it says so, sort no row as long as the vocabulary, and
    have each attention layer's one kernel call, and the other operators it
    lists, under their layer's scope."""
    import chip_smoke
    from paddle_tpu.obs import hlo_cost

    family = BY_KIND[kind]
    chip, step = family.chip, program.split("-")[0]
    eng, compiled = pool_programs(kind, program)
    hlo, mem, cache = compiled.as_text(), compiled.memory_analysis(), eng.cache
    held = [*cache.buffers(), *getattr(cache, "summary_buffers", list)()]
    sizes = [int(b._value().nbytes) for b in held]
    assert [tuple(b.shape) for b in held] == chip["buffers"]
    assert cache.nbytes() == sum(sizes)
    assert eng.buckets == chip.get("buckets", eng.buckets)

    def calls(kernel):
        return re.findall(r"%(" + kernel + r"(?:\.\d+)?) = ", hlo)

    if "pallas_calls" in chip:
        assert hlo.count(chip_smoke.PALLAS_CALL) == chip["pallas_calls"][step]
    kernels = chip.get("kernels", {}).get(step, {})
    for kernel, (least, most) in kernels.items():
        assert least <= len(calls(kernel)) <= most, kernel
    for kernel in chip.get("absent", {}).get(step, ()):
        assert not calls(kernel), kernel
    nbytes, shapes = chip["moves"]
    assert nbytes <= min(sizes)
    assert [m for m in chip_smoke.pool_sized_moves(hlo, nbytes)
            if not shapes or any(shape in m for shape in shapes)] == []
    written = chip["alias"](cache, program) if "alias" in chip \
        else cache.nbytes()
    assert mem.alias_size_in_bytes >= written
    if "temp" in chip:
        assert mem.temp_size_in_bytes < chip["temp"](cache, program)
    for text in chip.get("says", {}).get(step, ()):
        assert text in hlo, text
    for pattern in chip.get("forbid", {}).get(step, ()):
        assert not re.search(pattern, hlo), pattern
    if "sorts" in chip:
        assert vocab_sorts(hlo, chip["sorts"]) == []
    got = hlo_cost.scope_map(hlo)["instructions"]
    root = f"{family.model}/model/layers"
    if "attention_layers" in chip:
        kernel = next(iter(kernels))
        assert sorted(got[n] for n in calls(kernel)) == [
            (f"{root}/{i}/self_attn/{kernel}", "fwd")
            for i in chip["attention_layers"]]
    scopes = {scope for scope, _direction in got.values()}
    for under in chip.get("scoped", ()):
        assert any(s.startswith(f"{root}/{under}") for s in scopes), under


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

"""The spans of ``Engine.step`` and of an admission (``paddle_tpu.obs.spans``
rows: their order, their parents, what they carry), and the names and scopes
the engine's own compiled programs carry.  Split from ``test_obs_spans.py``
(the primitive, the surfaces, the programs compiled for the described chip)
along its sections, so that the two spread over two workers."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.obs import spans
from paddle_tpu.serving import Engine
from paddle_tpu.serving.tracing import RequestTracer

NAME, START, END, PARENT, ATTRS, SID = range(6)


def rows_since(t):
    return spans.snapshot(since=t)


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

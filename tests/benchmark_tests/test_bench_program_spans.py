"""The readers of the program's own spans and device-side names
(``harness/program_spans.py`` and the nine metrics that use it) on hand-built
ring rows and a hand-built trace; each reader returns ``None`` where what it
reads is absent (an older program, an untraced run)."""
import sys

import pytest

import bench_testlib  # noqa: F401
from benchmarks.harness import manifest as mf
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext

DECLARED = ["step_host_ms", "queue_wait_p50_ms", "prefill_host_ms",
            "decode_device_ms", "prefill_device_ms", "idle_outside_pull",
            "setup_trace_s"]
#: readers kept beside them, not in the manifest: on this stack a named scope
#: reaches no field of a fusion's or a ``while``'s trace event (PERF.md §7)
NEW_METRICS = DECLARED + ["ce_share", "optimizer_share"]
CLOCKS_APART = 1000.0       # the trace's clock minus perf_counter


def ctx():
    said = []
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True)
    c.say = said.append
    c.said = said
    return c


def row(name, start, end, parent=None, sid=None, **attrs):
    return (name, start, end, parent, attrs, sid)


#: four scheduler steps on ``perf_counter``: set-up's two misses, then
#: step 1 admits one request (10.00-10.30), steps 2-4 only decode
RING = [
    row("jit.trace", 1.0, 3.0, sid=1, fn="E.<locals>.prefill_step"),
    row("jit.compile", 3.0, 4.0, sid=2, fn="E.<locals>.prefill_step"),
    row("jit.trace", 4.0, 4.5, sid=3, fn="E.<locals>.decode_step"),
    row("jit.compile", 4.5, 6.5, sid=4, fn="E.<locals>.decode_step"),
    row("engine.reap", 10.00, 10.001, 10, 11),
    row("engine.prefix_lookup", 10.002, 10.003, 12, 13, hit_tokens=16),
    row("engine.prefill", 10.003, 10.023, 12, 14, bucket=32, attempts=1),
    row("engine.first_token", 10.030, 10.060, 12, 15),
    row("engine.admit", 10.001, 10.070, 10, 12, trace="e:r0", slot=0,
        prompt_tokens=40, queue_wait_ms=12.5, bucket=32, hit_tokens=16,
        outcome="admitted"),
    row("engine.prepare_decode", 10.070, 10.071, 10, 16),
    row("engine.decode", 10.071, 10.075, 10, 17, attempts=1),
    row("engine.pull", 10.075, 10.290, 10, 18),
    row("engine.deliver", 10.290, 10.299, 10, 19, retired=0),
    row("engine.step", 10.00, 10.30, None, 10, step=0, kv_tokens=0,
        admitted=1, running=1, queued=0),
]
for i, (t0, host) in enumerate([(10.40, 0.008), (10.50, 0.010),
                                (10.60, 0.030)]):
    sid = 20 + 10 * i
    RING += [
        row("engine.reap", t0, t0 + 0.001, sid, sid + 1),
        row("engine.prepare_decode", t0 + 0.001, t0 + 0.002, sid, sid + 2),
        row("engine.decode", t0 + 0.002, t0 + 0.004, sid, sid + 3),
        row("engine.pull", t0 + 0.004, t0 + 0.064, sid, sid + 4),
        row("engine.deliver", t0 + 0.064, t0 + 0.060 + host, sid, sid + 5),
        row("engine.step", t0, t0 + 0.060 + host, None, sid, step=i + 1,
            kv_tokens=41 + i, admitted=0, running=1 if i < 2 else 0,
            queued=0),
    ]
#: a failed admission, left out of every per-request median
RING.append(row("engine.admit", 10.75, 10.76, None, 60, trace="e:r1",
                queue_wait_ms=900.0, outcome="failed"))

#: the benchmark's own spans of the same four steps, on ``perf_counter``
BENCH = [("engine.step", 9.9999, 10.3001, {}), ("submit", 10.35, 10.36, {}),
         ("engine.step", 10.3999, 10.4681, {}),
         ("engine.step", 10.4999, 10.5701, {}),
         ("engine.step", 10.5999, 10.6901, {})]


def serve_trace():
    """The slice 10.35-10.72 (trace clock: + ``CLOCKS_APART``): the device
    runs a prefill program and two decode programs; it idles while the host
    delivers, reaps and prepares, and inside the second pull's tail."""
    c = CLOCKS_APART
    ops = [(c + 10.36, c + 10.39, "fusion.1", "%fusion.1 = f32[] fusion()"),
           (c + 10.404, c + 10.440, "fusion.2", "%fusion.2 = f32[] fusion()"),
           (c + 10.440, c + 10.460, "paged_decode_attention.3",
            "%paged_decode_attention.3 = bf16[] custom-call()"),
           (c + 10.504, c + 10.554, "fusion.2", "%fusion.2 = f32[] fusion()"),
           (c + 10.604, c + 10.664, "fusion.2", "%fusion.2 = f32[] fusion()")]
    host = [("engine.step", c + s, c + e, a) for n, s, e, a in BENCH
            if n == "engine.step" and s > 10.35] \
        + [(tr.SLICE, c + 10.35, c + 10.35, {}),
           (tr.SLICE, c + 10.72, c + 10.72, {})]
    mods = [(c + 10.36, c + 10.39, "jit_prefill_step(77)"),
            (c + 10.404, c + 10.460, "jit_decode_step(42)"),
            (c + 10.504, c + 10.554, "jit_decode_step(42)"),
            (c + 10.604, c + 10.664, "jit_decode_step(42)")]
    return tr.Trace({"/device:TPU:0": ops}, sorted(host, key=lambda s: s[1]),
                    {"/device:TPU:0": mods})


def serve_result(trace=None, spans=BENCH):
    return {"trace": trace, "spans": spans, "counters": {},
            "facts": {"kind": "serve", "window": [9.9, 10.8],
                      "quiet_window": [9.9, 10.72]}}


def train_result():
    """Two whole executions of the step's program, 0-4 and 5-9: the CE loop
    (a ``while`` and the fusions nested in it, scope in the op_name stat) and
    the optimizer's fusions (scope in the instruction's metadata)."""
    ce = 'tf_op: "jit(train_step)/transpose(jvp(loss.streamed_ce))/while"'
    ops = []
    for t in (0.0, 5.0):
        ops += [
            (t, t + 1.0, "fusion.1", "%fusion.1 = f32[] fusion()"),
            (t + 1.0, t + 2.0, "while.4", "%while.4 = () while() " + ce),
            (t + 1.2, t + 1.8, "fusion.9", "%fusion.9 = f32[] fusion() " + ce),
            (t + 2.0, t + 3.5, "fusion.2", "%fusion.2 = f32[] fusion()"),
            (t + 3.5, t + 4.0, "fusion.45",
             '%fusion.45 = f32[] fusion(), metadata={op_name="jit(train_step)'
             '/optimizer.adamw/mul"}'),
        ]
    mods = [(0.0, 4.0, "jit_train_step(9)"), (5.0, 9.0, "jit_train_step(9)"),
            (4.2, 4.3, "jit_convert(1)")]
    trace = tr.Trace({"/device:TPU:0": ops}, [("train_step", 0.0, 9.5, {})],
                     {"/device:TPU:0": mods})
    return {"trace": trace, "spans": [("train_step", 8.0, 8.1, {})],
            "counters": {}, "facts": {"kind": "train"}}


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(ps, "rows", lambda: list(RING))


def read(name, result, c=None):
    return mf.load_module("metrics", name).read(result, c or ctx())


# -- the harness file -----------------------------------------------------------

def test_children_named_and_seconds():
    kids = ps.children(RING)
    assert [r[ps.NAME] for r in kids[12]] == [
        "engine.prefix_lookup", "engine.prefill", "engine.first_token"]
    assert len(ps.named(RING, "engine.step")) == 4
    assert len(ps.named(RING, "engine.step", 10.35, 10.65)) == 2
    assert ps.seconds(ps.named(RING, "jit.trace")[0]) == pytest.approx(2.0)


def test_clock_offset_matches_the_interior_spans_by_their_durations():
    c = ctx()
    off = ps.clock_offset(serve_result(serve_trace()), c.say)
    assert off == pytest.approx(CLOCKS_APART, abs=1e-6)
    assert "3 bench.engine.step spans" in c.said[-1] and "at 1 of 4" in \
        c.said[-1]


@pytest.mark.parametrize("why", ["no trace", "too few spans", "bad match"])
def test_clock_offset_gives_nothing_it_cannot_stand_behind(why):
    if why == "no trace":
        res = serve_result(None)
    elif why == "too few spans":
        res = serve_result(serve_trace(), spans=BENCH[:2])
    else:       # the same count of spans, of other durations
        res = serve_result(serve_trace(), spans=[
            (n, s, e + 0.001 * i, a)
            for i, (n, s, e, a) in enumerate(BENCH)])
    assert ps.clock_offset(res) is None


def test_innermost_and_idle_by_span():
    trace = serve_trace()
    shifted = [(r[0], r[1] + CLOCKS_APART, r[2] + CLOCKS_APART) + r[3:]
               for r in RING]
    segs = ps.innermost(shifted, *trace.window())
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))
    by = ps.idle_by_span(trace, shifted)
    # 10.39-10.404: 10 ms outside any span, then reap, prepare, decode
    assert by["no_span"] == pytest.approx(
        0.01 + 0.01 + (0.5 - 0.468) + (0.6 - 0.57) + (0.72 - 0.69))
    assert by["engine.pull"] == pytest.approx(
        (0.464 - 0.460) + (0.564 - 0.554))
    assert sum(by.values()) == pytest.approx(
        sum(b - a for a, b in ps.idle_intervals(trace)))


def test_overlap_counts_nested_intervals_once():
    assert ps.overlap([(0, 2), (1, 3), (5, 6)], [(2.5, 5.5)]) == \
        pytest.approx(1.0)


# -- the readers ----------------------------------------------------------------

def test_step_host_ms_is_the_step_less_its_pull(ring):
    # decode-only steps that left slots running: 68 - 60 and 70 - 60 ms
    assert read("step_host_ms", serve_result()) == pytest.approx(9.0)


def test_queue_wait_and_prefill_host_take_admitted_requests_only(ring):
    assert read("queue_wait_p50_ms", serve_result()) == pytest.approx(12.5)
    assert read("prefill_host_ms", serve_result()) == pytest.approx(50.0)


def test_program_device_ms_finds_the_programs_by_name(ring):
    res = serve_result(serve_trace())
    assert read("decode_device_ms", res) == pytest.approx(56.0)
    assert read("prefill_device_ms", res) == pytest.approx(30.0)


def test_idle_outside_pull_leaves_out_what_a_pull_waits_for(ring):
    c = ctx()
    trace = serve_trace()
    idle = sum(b - a for a, b in ps.idle_intervals(trace))
    got = read("idle_outside_pull", serve_result(trace), c)
    assert got == pytest.approx(100 * (idle - 0.014) / 0.37)
    assert got < 100 * tr.idle_share(trace)
    assert "idle seconds by innermost program span" in c.said[-1]


def test_scope_shares_count_nested_events_once():
    res = train_result()
    assert read("ce_share", res) == pytest.approx(25.0)
    assert read("optimizer_share", res) == pytest.approx(12.5)


def test_setup_trace_s_sums_the_misses_before_the_window(ring):
    c = ctx()
    assert read("setup_trace_s", serve_result(), c) == pytest.approx(2.5)
    assert "jit.compile 3.00s" in c.said[-1]
    # a train result has no ``window`` fact: its first span opens the window
    assert read("setup_trace_s", train_result()) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_an_older_programs_run(name, monkeypatch):
    """The parent of the PR that brought these readers: no ring, programs
    called ``jit_program``, no scope on any event."""
    monkeypatch.setattr(ps, "rows", lambda: [])
    old = serve_trace()
    old.modules = {k: [(s, e, "jit_program(1)") for s, e, _n in v]
                   for k, v in old.modules.items()}
    plain = train_result()
    plain["trace"].device_ops = {
        k: [(s, e, n, n) for s, e, n, _t in v]
        for k, v in plain["trace"].device_ops.items()}
    for res in (serve_result(old), serve_result(None), plain):
        assert read(name, res) is None


def test_rows_are_empty_where_the_program_has_no_ring(monkeypatch):
    import paddle_tpu.obs

    # an older program: neither the module nor the package's attribute
    monkeypatch.setitem(sys.modules, "paddle_tpu.obs.spans", None)
    monkeypatch.delattr(paddle_tpu.obs, "spans", raising=False)
    assert ps.rows() == []


def test_rows_come_from_the_programs_ring():
    from paddle_tpu.obs import spans

    with spans.span("bench.test.row", n=1):
        pass
    assert ps.rows()[-1][:1] + ps.rows()[-1][4:5] == (
        "bench.test.row", {"n": 1})


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_loads_and_the_read_ones_are_declared(name):
    declared = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert callable(mf.load_module("metrics", name).read)
    assert (name in declared) == (name in DECLARED)
    if name in declared:
        assert declared[name]["source"] in mf.SOURCES

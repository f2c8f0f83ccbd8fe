"""Device time by scope (``harness/device_scopes.py`` and the seven readers
that came with it) on a hand-built trace — a ``while`` event over its body's
events, two programs that share an instruction name — and hand-built scope
maps; every reader returns ``None`` where the program states no map."""
import pytest

import bench_testlib  # noqa: F401
from benchmarks.harness import device_scopes as ds
from benchmarks.harness import manifest as mf
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext

TRACE_READERS = {           # name -> cells' kind it is listed for
    "ce_device_ms": "train", "optimizer_device_ms": "train",
    "device_unscoped.train": "train", "dsa_select_ms": "serve",
    "head_device_ms": "serve", "device_unscoped.serve": "serve"}
NEW_METRICS = sorted(TRACE_READERS) + ["latent_upprojected_share"]


def ctx():
    said = []
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True)
    c.say = said.append
    c.said = said
    return c


def read(name, result, c=None):
    return mf.load_module("metrics", name).read(result, c or ctx())


def op(start, end, name):
    return (start, end, name, f"%{name} = f32[] fusion()")


def train_trace():
    """Two whole executions of the step (0-4 s, 5-9 s) and one of a small
    program that shares the name ``fusion.1`` with it.  In a step: a layer's
    fusion (1 s), the CE's forward ``while`` 1.0-2.0 over two trips of its
    body's ``fusion.9`` (0.3 s each), the backward ``while`` 2.0-3.0 over one
    ``fusion.10`` (0.5 s), the optimizer (0.5 s), an unscoped copy (0.25 s),
    an instruction the map lacks (0.125 s); 0.125 s idle."""
    ops = []
    for t in (0.0, 5.0):
        ops += [op(t, t + 1.0, "fusion.1"),
                op(t + 1.0, t + 2.0, "while.4"),
                op(t + 1.1, t + 1.4, "fusion.9"),
                op(t + 1.5, t + 1.8, "fusion.9"),
                op(t + 2.0, t + 3.0, "while.3"),
                op(t + 2.25, t + 2.75, "fusion.10"),
                op(t + 3.0, t + 3.5, "fusion.45"),
                op(t + 3.5, t + 3.75, "copy.7"),
                op(t + 3.75, t + 3.875, "fusion.77")]
    ops.append(op(4.2, 4.3, "fusion.1"))
    mods = [(0.0, 4.0, "jit_train_step(9)"), (5.0, 9.0, "jit_train_step(9)"),
            (4.2, 4.3, "jit_convert(1)")]
    return tr.Trace({"/device:TPU:0": sorted(ops)},
                    [("train_step", 0.0, 9.5, {})],
                    {"/device:TPU:0": mods})


TRAIN_MAP = {"module": "jit_train_step", "instructions": {
    "fusion.1": ("gpt/layers/3/mlp/fc1", "fwd"),
    "while.4": ("loss.streamed_ce", "fwd"),
    "fusion.9": ("loss.streamed_ce", "fwd"),
    "while.3": ("loss.streamed_ce", "bwd"),
    "fusion.10": ("loss.streamed_ce", "bwd"),
    "fusion.45": ("optimizer.adamw", "fwd"),
    "copy.7": ("", "fwd"),
    "never.ran": ("gpt/layers/0/attn", "fwd")}}


def serve_trace():
    """Three executions of ``jit_decode_step`` (10 ms each: 4 ms of a layer,
    a ``conditional`` of 3 ms over a 2 ms ``dsa`` kernel, 2 ms of the head,
    1 ms idle) and two of ``jit_prefill_step`` from two buckets' programs,
    whose ``fusion.2`` the two maps read differently and whose ``fusion.3``
    only the second holds."""
    ops, mods = [], []
    for t in (0.0, 0.02, 0.04):
        mods.append((t, t + 0.010, "jit_decode_step(42)"))
        ops += [op(t, t + 0.004, "fusion.2"),
                op(t + 0.004, t + 0.007, "conditional.5"),
                op(t + 0.0045, t + 0.0065, "dsa_sparse_decode.8"),
                op(t + 0.007, t + 0.009, "fusion.536")]
    mods += [(0.10, 0.13, "jit_prefill_step(7)"),
             (0.20, 0.23, "jit_prefill_step(8)")]
    ops += [op(0.10, 0.12, "fusion.2"), op(0.12, 0.13, "fusion.6"),
            op(0.20, 0.21, "fusion.2"), op(0.21, 0.22, "fusion.3"),
            op(0.22, 0.23, "fusion.6")]
    return tr.Trace({"/device:TPU:0": sorted(ops)}, [],
                    {"/device:TPU:0": sorted(mods)})


SERVE_MAPS = [
    {"module": "jit_decode_step", "instructions": {
        "fusion.2": ("M/model/layers/0/mlp", "fwd"),
        "conditional.5": ("M/model/layers/0/self_attn/dsa.select", "fwd"),
        "dsa_sparse_decode.8":
            ("M/model/layers/0/self_attn/dsa.attend/dsa_sparse_decode",
             "fwd"),
        "fusion.536": ("M/model.head", "fwd")}},
    {"module": "jit_prefill_step", "instructions": {
        "fusion.2": ("M/model/layers/0/mlp", "fwd"),
        "fusion.6": ("M/model.head", "fwd")}},
    {"module": "jit_prefill_step", "instructions": {
        "fusion.2": ("M/model/layers/0/self_attn", "fwd"),
        "fusion.3": ("M/model/layers/1/mlp", "fwd"),
        "fusion.6": ("M/model.head", "fwd")}},
]


def result(kind, trace, maps, monkeypatch):
    monkeypatch.setattr(ds, "program_maps", lambda _trace: maps)
    return {"trace": trace, "spans": [], "counters": {},
            "facts": {"kind": kind, "window": [0.0, 10.0],
                      "quiet_window": [0.0, 10.0]}}


# -- the arithmetic --------------------------------------------------------------

@pytest.mark.parametrize("events,want", [
    # a child's time is taken out of its parent's
    ([(0, 10, "while"), (2, 5, "body")], {"while": 7, "body": 3}),
    # two trips of a body, then the parent's own tail
    ([(0, 10, "w"), (1, 2, "b"), (3, 4, "b"), (4, 9, "c")],
     {"w": 3, "b": 2, "c": 5}),
    # three deep
    ([(0, 8, "a"), (1, 7, "b"), (2, 3, "c")], {"a": 2, "b": 5, "c": 1}),
    # side by side with a gap: nobody gets the gap
    ([(0, 1, "a"), (3, 4, "b")], {"a": 1, "b": 1}),
    # an event that outlasts the one it started in keeps what is its own
    ([(0, 4, "a"), (2, 6, "b")], {"a": 2, "b": 4}),
    # the same instant: the later in the list is inside
    ([(0, 4, "a"), (0, 4, "b")], {"b": 4}),
])
def test_every_moment_goes_to_the_innermost_open_event(events, want):
    got = ds.innermost_seconds(events)
    assert {k: v for k, v in got.items() if v} == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(
        sum(e - s for s, e in tr.union((s, e) for s, e, _k in events)))


def test_the_scopes_sum_is_the_programs_busy_time():
    trace = train_trace()
    got = ds.by_program(trace, [TRAIN_MAP])
    step = got["jit_train_step"]
    assert step["runs"] == 2
    assert sum(step["seconds"].values()) == pytest.approx(step["busy_s"])
    runs = ps.program_runs(trace, "jit_train_step")
    assert step["busy_s"] == pytest.approx(
        sum(tr.busy_within(trace, s, e) for s, e in runs))
    # the whiles keep what their bodies' events leave: 0.4 and 0.5 s a step
    assert step["seconds"]["loss.streamed_ce", "fwd"] == pytest.approx(2.0)
    assert step["seconds"]["loss.streamed_ce", "bwd"] == pytest.approx(2.0)
    assert step["seconds"][ds.UNSCOPED, "fwd"] == pytest.approx(0.5)
    assert step["seconds"][ds.NOT_IN_MAP, "fwd"] == pytest.approx(0.25)
    # the small program shares ``fusion.1`` and has no map: the join is by
    # program, so the step's reading is not lent to it
    assert got["jit_convert"]["seconds"] == {
        (ds.NO_MAP, "fwd"): pytest.approx(0.1)}


@pytest.mark.parametrize("last,kept", [
    # the device's record ends inside the last execution: left out
    ((9.5, 9.6, "jit_train_step(9)"), 2),
    # another program's short execution at the end is whole
    ((9.5, 9.6, "jit_convert(1)"), 3),
    # as long as the others: whole
    ((9.5, 13.0, "jit_train_step(9)"), 3),
])
def test_an_execution_the_records_end_cut_short_is_left_out(last, kept):
    mods = [(0.0, 4.0, "jit_train_step(9)"), (5.0, 9.0, "jit_train_step(9)"),
            last]
    assert len(ds.whole_runs(mods, last[1])) == kept
    assert len(ds.whole_runs(mods, last[1] + 0.5)) == 3   # events follow it
    trace = train_trace()
    trace.device_ops["/device:TPU:0"].append(op(9.5, 9.6, "fusion.1"))
    trace.modules["/device:TPU:0"].append((9.5, 9.6, "jit_train_step(9)"))
    got = ds.by_program(trace, [TRAIN_MAP])["jit_train_step"]
    assert got["runs"] == 2 and got["busy_s"] == pytest.approx(7.75)


@pytest.mark.parametrize("scope,want", [
    ("gpt/layers/3/attn/qkv_proj", "gpt/layers/*/attn/qkv_proj"),
    ("h/12", "h/*"), ("loss.streamed_ce", "loss.streamed_ce"),
    ("M/model/layers/0/mlp/experts.3", "M/model/layers/*/mlp/experts.3"),
    ("", "")])
def test_a_scope_folds_by_layer_kind(scope, want):
    assert ds.fold(scope) == want


def test_the_table_is_per_execution_forward_and_backward():
    got = ds.by_program(train_trace(), [TRAIN_MAP])
    lines = ds.table("jit_train_step", got["jit_train_step"])
    assert "2 whole executions" in lines[0] and "3875.000 ms busy" in lines[0]
    assert "9.68 % under no scope" in lines[0]      # 0.375 of 3.875 s
    assert lines[1].split() == ["1000.0000", "|", "1000.0000",
                                "loss.streamed_ce"]
    assert lines[2].split() == ["1000.0000", "|", "0.0000",
                                "gpt/layers/*/mlp/fc1"]
    assert len(lines) == 6
    short = ds.table("jit_train_step", got["jit_train_step"], rows=2)
    assert short[-1].split()[-3:] == ["(3", "more", "scopes)"]


def test_same_named_programs_are_told_by_their_instructions():
    got = ds.by_program(serve_trace(), SERVE_MAPS)["jit_prefill_step"]
    assert got["runs"] == 2
    # the second execution ran ``fusion.3``: only the second map fits it.
    # The first fits both maps, which disagree on ``fusion.2``: ambiguous
    assert got["seconds"] == {
        (ds.AMBIGUOUS, "fwd"): pytest.approx(0.02),
        ("M/model.head", "fwd"): pytest.approx(0.02),
        ("M/model/layers/0/self_attn", "fwd"): pytest.approx(0.01),
        ("M/model/layers/1/mlp", "fwd"): pytest.approx(0.01)}


# -- the readers -------------------------------------------------------------------

@pytest.mark.parametrize("name,want", [
    ("ce_device_ms", 2000.0), ("optimizer_device_ms", 500.0),
    # 0.5 unscoped + 0.25 not in the map + 0.1 without a map, of 7.85 s
    ("device_unscoped.train", 100.0 * 0.85 / 7.85)])
def test_a_train_reader_reads_its_scope_of_the_step(name, want, monkeypatch):
    c = ctx()
    res = result("train", train_trace(), [TRAIN_MAP], monkeypatch)
    assert read(name, res, c) == pytest.approx(want)
    assert any(line.startswith("device time by scope: jit_train_step")
               for line in c.said)
    assert c.said[0].startswith("device time by scope: 1 scope maps")
    said = len(c.said)
    read(name, res, c)                    # computed, and printed, once
    assert not [ln for ln in c.said[said:] if ln.startswith("device time")]


def test_a_small_program_prints_one_line_and_no_table(monkeypatch):
    c = ctx()
    monkeypatch.setattr(ds, "SMALL", 0.05)          # jit_convert: 1.3 %
    ds.read(result("train", train_trace(), [TRAIN_MAP], monkeypatch), c.say)
    assert not [ln for ln in c.said if "jit_convert, 1 whole" in ln]
    assert c.said[-1].endswith("jit_convert 0.10000 (1)")


@pytest.mark.parametrize("name,want", [
    # a conditional keeps what its kernel leaves: 1 ms a step
    ("dsa_select_ms", 1.0), ("head_device_ms", 2.0),
    # the first prefill's ambiguous 20 ms of 27 + 60 ms busy
    ("device_unscoped.serve", 100.0 * 0.02 / 0.087)])
def test_a_serve_reader_reads_the_decode_program(name, want, monkeypatch):
    res = result("serve", serve_trace(), SERVE_MAPS, monkeypatch)
    assert read(name, res) == pytest.approx(want)


def test_a_scope_is_matched_by_its_whole_part(monkeypatch):
    res = result("serve", serve_trace(), SERVE_MAPS, monkeypatch)
    got = ds.read(res)["jit_decode_step"]
    assert ds.scope_ms(got, "dsa.attend") == pytest.approx(2.0)
    assert ds.scope_ms(got, "dsa") is None
    assert ds.scope_ms(got, "model") == pytest.approx(7.0)
    assert ds.program_scope_ms(res, "jit_never_ran", "model") is None


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
@pytest.mark.parametrize("why", ["older program", "no trace", "no modules"])
def test_a_reader_finds_nothing_without_a_map(name, why, monkeypatch):
    import paddle_tpu.obs

    trace = train_trace() if TRACE_READERS[name] == "train" else serve_trace()
    if why == "older program":      # the parent of this PR: no such function
        monkeypatch.delattr(paddle_tpu.obs, "scope_maps")
    elif why == "no trace":
        trace = None
    else:
        trace.modules = {}
    res = {"trace": trace, "spans": [], "counters": {},
           "facts": {"kind": TRACE_READERS[name]}}
    assert read(name, res) is None


def test_the_maps_are_asked_of_the_program_by_the_traces_module_names(
        monkeypatch):
    import paddle_tpu.obs

    asked = []
    monkeypatch.setattr(paddle_tpu.obs, "scope_maps",
                        lambda names: asked.append(names) or [])
    assert ds.program_maps(serve_trace()) == []
    assert asked == [{"jit_decode_step", "jit_prefill_step"}]


# -- the counter's reader ----------------------------------------------------------

def prefill(start, **attrs):
    return ("engine.prefill", start, start + 0.01, None, attrs, None)


@pytest.mark.parametrize("rows,want", [
    ([prefill(1.0, latent_pairs_upprojected=300, latent_pairs_absorbed=0),
      prefill(2.0, latent_pairs_upprojected=100, latent_pairs_absorbed=100)],
     80.0),
    # outside the quiet window, and a program without the counts
    ([prefill(1.0, latent_pairs_upprojected=50, latent_pairs_absorbed=50),
      prefill(11.0, latent_pairs_upprojected=900, latent_pairs_absorbed=0),
      prefill(3.0, bucket=32)], 50.0),
    ([prefill(1.0, bucket=32)], None), ([], None)])
def test_latent_upprojected_share_sums_the_windows_prefills(rows, want,
                                                            monkeypatch):
    monkeypatch.setattr(ps, "rows", lambda: rows)
    res = {"trace": None, "spans": [], "counters": {},
           "facts": {"kind": "serve", "quiet_window": [0.0, 10.0]}}
    got = read("latent_upprojected_share", res)
    assert got == (None if want is None else pytest.approx(want))
    assert read("latent_upprojected_share",
                {**res, "facts": {"kind": "serve"}}) is None


# -- the manifest ------------------------------------------------------------------

@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_loads_and_is_declared(name):
    declared = {m["name"]: m for m in mf.load_manifest()["per_layer"]}
    assert callable(mf.load_module("metrics", name).read)
    m = declared[name]
    assert m["source"] == ("program_counter" if name.startswith("latent")
                           else "device_trace")
    e2e = {e["name"]: e for e in mf.load_manifest()["end_to_end"]}
    for cell in m["workloads"]:           # each cell reports what it moves
        assert cell in e2e[m["moves"]]["workloads"]

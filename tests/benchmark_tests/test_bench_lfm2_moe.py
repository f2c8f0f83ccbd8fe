"""The ``lfm2_moe`` family (the code that runs LFM2-24B-A2B) through the
``serve_resident`` driver at tiny size in bf16: a sound run is ``correct``
with every prefix's probe hitting the whole prefix — every piece behind the
snapshot the piece before left — the fp8 control and a run whose decode steps
never shift the state are not, the four per-layer readers the
family brings read a CPU run's spans and a hand-built trace and return
``None`` where the program gives them nothing, and the reference's own short
cut (the shared opening, behind its keys and its last two columns) equals the
long way."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, tiny
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext
from benchmarks.harness.manifest import load_manifest, load_module

#: bf16, as served, at a hidden size of 256 (at the tiny preset's 64 a
#: layer adds less to the residual stream than the token's own embedding, and
#: under the tied head every greedy token is the last one again: nothing
#: moves): a sound run reads 0 / -0.016 to -0.037 on the two numbers, the fp8
#: control 0 to 0.055 / 0.12 to 0.14, decode steps that leave the state as it
#: was 0.98 on the second
LIMITS = {"served_logit_gap": 0.02, "sampled_topk_gap": 0.05}
WIDER = dict(hidden_size=256, intermediate_size=256, moe_intermediate_size=64)
DOC = 64          # a resident prefix: four strides of 16
CELL = "lfm2-24b-a2b-s20.serve-agent-open"
NEW = ("conv_device_ms", "state_restored_share", "state_snapshot_peak",
       "hybrid_decode_roofline")


def resident_mix() -> dict:
    """Sixteen requests from four clients, every one a tail of 16-40 tokens
    behind one of two 64-token prefixes; answers of 8-24 tokens."""
    mix = dict(tiny("tiny_serve_mix"), kind="serve_resident", limits=LIMITS,
               check_requests=12, reference_pad=128,
               resident={"piece_tokens": 32})
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4, "requests_per_client": 4},
        prompt_tokens={"median": 84, "sigma": 0.1, "min": 80, "max": 104},
        output_tokens={"median": 14, "sigma": 0.4, "min": 8, "max": 24},
        max_total_tokens=128,
        shared_prefixes={"count": 2, "tokens": DOC, "share": 1.0})
    mix["engine"] = dict(mix["engine"], max_seq=128, min_bucket=16,
                         num_kv_blocks=80, num_state_snapshots=48)
    mix["warmup_buckets"] = [16, 32, 48]
    return mix


def run(tmp_path, sabotage=None, **kw):
    """One run of the small cell, the snapshot stride at 16 (a pool reads the
    constant when the driver builds its engine) so that the 64-token prefix
    passes four."""
    from paddle_tpu.serving import group_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(group_cache, "SNAPSHOT_STRIDE", 16)
        return _run(tmp_path, sabotage, **kw)


def _run(tmp_path, sabotage, **kw):
    ctx = RunContext(
        config=dict(tiny("tiny_lfm2_moe"), torch_dtype="bfloat16", **WIDER),
        mix=resident_mix(), limits=LIMITS, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 40, seconds=7.0,
        sabotage=sabotage, **kw)
    return load_module("drivers", "serve_resident").run(ctx), ctx


def test_the_family_serves_through_the_resident_driver_and_is_correct(
        tmp_path):
    res, ctx = run(tmp_path, control=True)
    assert res["checks"].correct, res["checks"].rows
    assert res["attempted"] == 16 and res["failed"] == 0
    assert {r[0] for r in res["checks"].rows} == {
        "checked_requests", "served_logit_gap", "sampled_topk_gap"}
    f = res["facts"]
    assert f["resident"]["probe_hits"] == [DOC, DOC]
    assert (f["dims"]["attn_layers"], f["dims"]["layers"],
            f["dims"]["dense_layers"], f["dims"]["taps"]) == (1, 4, 1, 3)
    assert f["dims"]["held"] == (0, 4)
    # every request hit its prefix whole
    assert res["counters"]["prefix_end"]["hit_tokens"] \
        - res["counters"]["prefix_start"]["hit_tokens"] == 16 * DOC
    # the lower precision fails one of the cell's numbers
    assert any(f["control_gaps"][k] > v for k, v in LIMITS.items()), \
        f["control_gaps"]
    # the program's own spans carry what the state group did: the new
    # readers of program counters read a CPU run
    quiet = dict(res, facts=dict(f, quiet_window=f["window"]))
    share = load_module("metrics", "state_restored_share")
    got = share.prefills(quiet)
    assert len(got) == 16 and all(a["state_row"] > 0 for a in got)
    assert share.read(quiet, ctx) == 100.0
    peak = load_module("metrics", "state_snapshot_peak").read(quiet, ctx)
    assert 10.0 < peak <= 100.0
    for name in ("conv_device_ms", "hybrid_decode_roofline"):
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


def test_decode_steps_that_leave_the_state_as_it_was_are_not_correct(
        tmp_path, monkeypatch):
    """The program's decode steps filter behind the state their prefill left
    and never shift it: every generated token's taps are stale."""
    from paddle_tpu.serving import group_cache

    real = group_cache.StatePool.decode_update

    def stale(self, layer_idx, z, active):
        before = self.state._value()
        taps = real(self, layer_idx, z, active)
        self.state._set_data(before)
        return taps

    monkeypatch.setattr(group_cache.StatePool, "decode_update", stale)
    res, _ctx = run(tmp_path)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


# -- the readers on a hand-built result ---------------------------------------

DIMS = {"layers": 4, "attn_layers": 1, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "hidden": 64, "dense_layers": 1, "moe_ffn": 32,
        "top_k": 4}
SHIFT = 1000.0              # the trace's clock minus perf_counter


def quiet_ctx():
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    c.say = lambda _msg: None
    return c


def synthetic(monkeypatch, *, attrs=True):
    """Four decode steps of 10 ms from t = 10 s, 2 running slots holding
    17,000 tokens, 4 layers of which one is attention: each step launches
    ``paged_decode_attention`` once (400 us); five admissions before them,
    four of which started from a snapshot."""
    ring, bench, ops, host = [], [], [], []
    for i in range(5):
        t = 9.5 + 0.01 * i
        fill = dict(state_row=7 * (i > 0), state_snapshots_written=2,
                    state_hit_given_up=16 * (i == 0)) if attrs else {}
        ring.append(("engine.prefill", t, t + 0.005, None,
                     dict(bucket=256, **fill), 50 + i))
    for i in range(4):
        t = 10.0 + 0.01 * i
        state = dict(swa_full_rows=17000 + i, swa_window_rows=0,
                     swa_context=17000 + i, swa_blocks_used=[900 + 50 * i],
                     swa_blocks=[2000], state_slots=2,
                     state_snapshots_used=300 + 10 * i,
                     state_snapshots=1000) if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=2, **state), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 17000, "running": 2}))
        s = t + SHIFT + 0.001
        ops.append((s, s + 4e-4, "paged_decode_attention.2",
                    "%paged_decode_attention.2 = bf16[2,2,2,128]"
                    "{3,2,1,0} custom-call()"))
    host = [("engine.step", 9.98 + SHIFT, 9.985 + SHIFT, {})] + host + \
        [("engine.step", 10.07 + SHIFT, 10.075 + SHIFT, {})]
    monkeypatch.setattr(ps, "rows", lambda: ring)
    return {"trace": tr.Trace({"/device:TPU:0": sorted(ops)}, host, {}),
            "spans": bench,
            "facts": {"dims": DIMS, "num_slots": 4, "kv_itemsize": 2,
                      "window": [9.0, 11.0], "quiet_window": [9.0, 11.0]}}


def test_the_new_readers_read_a_synthetic_result(monkeypatch):
    res, c = synthetic(monkeypatch), quiet_ctx()
    read = lambda name: load_module("metrics", name).read(res, c)  # noqa
    assert read("state_restored_share") == pytest.approx(80.0)
    assert read("state_snapshot_peak") == pytest.approx(33.0)
    # (the accepted reader of the groups that have blocks reads the K/V
    # group's here: 1,050 of 2,000 at the peak)
    assert read("cache_group_peak") == pytest.approx(52.5)
    # 4 events = 4 whole steps of 1 attention layer; a token's key and value
    # of 2 heads x 16 in bf16
    rows = sum(17000 + i for i in range(4))
    by_bytes = rows * 2 * 2 * 16 * 2 / 819e9
    assert read("hybrid_decode_roofline") == pytest.approx(
        100.0 * by_bytes / (4 * 4e-4), rel=1e-6)
    # no scope map in a hand-built trace: nothing to join
    assert read("conv_device_ms") is None


def test_conv_device_ms_reads_its_scope_off_a_joined_trace():
    """The device time of a whole ``jit_decode_step`` under the conv layers'
    scopes: their projections, ``conv.mix`` and ``state.write``, and nothing
    of an attention layer or of another program."""
    from benchmarks.harness import device_scopes as ds

    root = "Lfm2ForCausalLM/model/layers"
    got = {"jit_decode_step": {"runs": 2, "busy_s": 0.02, "seconds": {
        (f"{root}/0/conv/in_proj", "fwd"): 0.002,
        (f"{root}/0/conv/conv.mix", "fwd"): 0.001,
        (f"{root}/0/conv/state.write", "fwd"): 0.0004,
        (f"{root}/3/conv/out_proj", "fwd"): 0.0006,
        (f"{root}/2/self_attn/paged_decode_attention", "fwd"): 0.01,
        ("model.head", "fwd"): 0.006}},
        "jit_prefill_step": {"runs": 1, "busy_s": 0.01, "seconds": {
            (f"{root}/0/conv/in_proj", "fwd"): 0.01}}}
    res = {ds._KEY: got}
    reader = load_module("metrics", "conv_device_ms")
    assert reader.read(res, quiet_ctx()) == pytest.approx(
        1e3 * (0.002 + 0.001 + 0.0004 + 0.0006) / 2)
    del got["jit_decode_step"]["seconds"]
    got["jit_decode_step"]["seconds"] = {("model.head", "fwd"): 0.006}
    assert reader.read(res, quiet_ctx()) is None


def test_the_new_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent commit: no such attribute on a span, another family's
    dims, no such kernel in the trace."""
    res, c = synthetic(monkeypatch, attrs=False), quiet_ctx()
    for name in NEW:
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    res["facts"]["dims"] = {"layers": 24, "heads": 16, "kv_heads": 16,
                            "head_dim": 64}
    assert load_module("metrics", "hybrid_decode_roofline").read(res, c) \
        is None
    res = synthetic(monkeypatch)
    res["trace"] = tr.Trace(
        {"/device:TPU:0": [(1010.0, 1010.001, "fusion.1", "%fusion.1 = ")]},
        res["trace"].host_spans, {})
    assert load_module("metrics", "hybrid_decode_roofline").read(res, c) \
        is None
    res = synthetic(monkeypatch)
    monkeypatch.setattr(ps, "rows", lambda: [])         # a program with no ring
    for name in NEW:
        assert load_module("metrics", name).read(res, c) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = load_manifest()
    by_name = {e["name"]: e for e in m["per_layer"] + m["end_to_end"]}
    for name in NEW + ("ttft_p50_ms", "ttft_p95_ms", "prefix_hit_tokens",
                       "queue_wait_p50_ms", "prefill_host_ms",
                       "prefill_device_ms", "setup_trace_s",
                       "device_unscoped.serve", "moe_experts_roofline",
                       "moe_experts_touched", "moe_held_share",
                       "head_device_ms"):
        assert CELL in by_name[name]["workloads"], name
    # those readers charge every layer, or read a window group's attributes
    # (``cache_group_peak`` would read the K/V group's blocks here, but the
    # accepted ``test_bench_mellum.py`` holds its list to the ide cell alone)
    for name in ("paged_decode_roofline", "swa_decode_roofline",
                 "swa_attended_share", "cache_group_peak", "tpot_p50_ms"):
        assert CELL not in by_name[name]["workloads"]
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "ttft_p50_ms"
    cfg = {c["name"]: c for c in m["configs"]}["lfm2-24b-a2b-s20"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "max_position_embeddings"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2-24b-a2b-s20", "agent-open", 1)


def test_the_configuration_keeps_every_published_width_and_the_first_stage():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-24b-a2b-s20.json")) as f:
        cfg = json.load(f)
    ref = load_module("references", "lfm2_moe")
    d = ref.dims(cfg)
    assert (d["layers"], d["attn_layers"], d["dense_layers"], d["taps"]) == \
        (20, 5, 2, 3)
    assert [i for i, k in enumerate(d["kinds"]) if k == ref.ATTENTION] == \
        [2, 6, 10, 14, 18]
    assert (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"], d["ffn"],
            d["moe_ffn"], d["vocab"]) == (2048, 32, 8, 64, 11776, 1536, 65536)
    assert (d["experts"], d["held"], d["top_k"], d["route_scale"]) == \
        (64, (0, 8), 4, 1.0)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["max_position_embeddings"]) == (40, 64, 128000)
    assert pub["layer_types"][:20] == cfg["layer_types"] \
        and len(pub["layer_types"]) == 40
    assert set(cfg["reduced"]) == set(pub)
    shapes = ref.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert 1.94e9 < params < 1.95e9                     # 3.89 GB in bf16
    with open(os.path.join(ROOT, "benchmarks", "mixes",
                           "agent-open.json")) as f:
        mix = json.load(f)
    eng = mix["engine"]
    kv = eng["num_kv_blocks"] * 5 * 16 * 4096           # 64 in 128 lanes
    snaps = eng["num_state_snapshots"] * 15 * 2 * 2048 * 2
    assert 7.3e9 <= kv <= 7.5e9 and 0.49e9 <= snaps <= 0.52e9
    assert 11.5e9 <= 2 * params + kv + snaps <= 12.1e9
    sp = mix["params"]["shared_prefixes"]
    assert (sp["count"], sp["tokens"], sp["share"]) == (32, 8192, 1.0)
    assert eng["num_kv_blocks"] == 32 * 512 + 64 * 96


def test_the_programs_model_takes_every_seeded_leaf_and_nothing_else():
    """The adapter lays the reference's tree out under the program's keys:
    every leaf of both, shapes alike, the head tied (no key of its own)."""
    from benchmarks.adapters import _load
    from benchmarks.harness import weights

    ref = load_module("references", "lfm2_moe")
    adapter = load_module("adapters", "lfm2_moe")
    cfg = tiny("tiny_lfm2_moe")
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), 3, jnp.float32)
    model = adapter.build_model(cfg)
    n = _load.load(model, adapter, tree, d)
    assert n == sum(int(v.size) for v in tree.values())
    assert "lm_head" not in model.state_dict()
    bias = model.state_dict()["model.layers.1.feed_forward.expert_bias"]
    assert float(jnp.abs(bias._value()).max()) > 0      # seeded, not zero


def test_a_shared_opening_goes_through_once_and_changes_nothing():
    """Three sequences, two of which open with the same 1,152 tokens: the
    hidden states equal those of each sequence alone (the remainders start
    behind the opening's keys and, in a conv layer, its last two columns)."""
    from benchmarks.harness import weights

    ref = load_module("references", "lfm2_moe")
    cfg = dict(tiny("tiny_lfm2_moe"), max_position_embeddings=2048)
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), 5, jnp.float32)
    rng = np.random.default_rng(6)
    doc = rng.integers(0, 512, (1152,), dtype=np.int32)
    seqs = [np.concatenate([doc, rng.integers(0, 512, (128,), np.int32)]),
            rng.integers(0, 512, (1280,), dtype=np.int32),
            np.concatenate([doc, rng.integers(0, 512, (128,), np.int32)])]
    assert ref.shared_openings(seqs) == [(1152, [0, 2]), (0, [1])]
    with jax.default_matmul_precision("highest"):
        many = ref.hidden_many(lambda names: {n: tree[n] for n in names},
                               [jnp.asarray(s) for s in seqs], d)
        for s, got in zip(seqs, many):
            alone = ref.hidden(tree, jnp.asarray(s), d)
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       atol=2e-5)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "references",
                           "lfm2_moe.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.replace("imports nothing of", "")
    assert "import" in src and "pallas" not in src

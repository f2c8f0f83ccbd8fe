"""The ``mellum`` family (the code that runs Mellum2-12B-A2.5B) through the
``serve_resident`` driver at tiny size in bf16: a sound run is ``correct``
with every document's probe hitting the whole document by the hit of two
kinds and cold requests in the same queue, the fp8 control and a run whose
sliding layers ignore their window are not, the four per-layer readers the
family brings read a CPU run's spans and a hand-built trace and return
``None`` where the program gives them nothing, and the reference's own short
cuts (a window's keys a block, the shared opening) equal the long way."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import tiny
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext
from benchmarks.harness.manifest import load_manifest, load_module

#: bf16, as served: a sound run reads 0.0001 / -0.004 on the two numbers, the
#: fp8 control 0.025 / 0.029, sliding layers with no window over 0.06
LIMITS = {"served_logit_gap": 0.004, "sampled_topk_gap": 0.008}
DOC = 64          # a document: nearly three windows of 24, YaRN's original 64
CELL = "mellum2-12b-a2.5b-e8.serve-ide-open"
NEW = ("swa_attended_share", "swa_decode_roofline", "swa_prefill_roofline",
       "cache_group_peak")


def resident_mix() -> dict:
    """Sixteen requests from four clients: three of four a 16-token tail
    behind one of two 64-token documents, the rest cold prompts of 12-40
    tokens in the same queue; answers of 8-24 tokens."""
    mix = dict(tiny("tiny_serve_mix"), kind="serve_resident", limits=LIMITS,
               check_requests=12, reference_pad=128,
               resident={"piece_tokens": 32})
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4, "requests_per_client": 4},
        prompt_tokens={"median": 20, "sigma": 0.5, "min": 12, "max": 40},
        output_tokens={"median": 14, "sigma": 0.4, "min": 8, "max": 24},
        max_total_tokens=128,
        shared_prefixes={"count": 2, "tokens": DOC, "share": 0.75})
    mix["engine"] = dict(mix["engine"], max_seq=128, min_bucket=16,
                         num_kv_blocks=80, num_window_blocks=40)
    mix["warmup_buckets"] = [16, 32, 48]
    return mix


def run(tmp_path, sabotage=None, **kw):
    ctx = RunContext(
        config=dict(tiny("tiny_mellum"), torch_dtype="bfloat16"),
        mix=resident_mix(), limits=LIMITS, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 38, seconds=12.0,
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
    assert f["dims"]["window"] == 24 and f["dims"]["full_layers"] == 1
    assert f["dims"]["held"] == (0, 4)
    # the twelve shared requests hit their document whole, the four cold
    # ones nothing
    assert res["counters"]["prefix_end"]["hit_tokens"] \
        - res["counters"]["prefix_start"]["hit_tokens"] == 12 * DOC
    # the lower precision fails one of the cell's numbers
    assert any(f["control_gaps"][k] > v for k, v in LIMITS.items()), \
        f["control_gaps"]
    # the program's own decode steps carry what their layers read: the new
    # readers of program counters read a CPU run
    share = load_module("metrics", "swa_attended_share")
    quiet = dict(res, facts=dict(f, quiet_window=f["window"]))
    steps = share.steps(quiet)
    assert steps and all(0 < a["swa_window_rows"] <= a["swa_full_rows"]
                         == a["swa_context"] for a in steps)
    assert any(a["swa_window_rows"] < a["swa_full_rows"] for a in steps)
    # one full layer of four: between 25 % (long slots) and 100 % (short)
    assert 40.0 < share.read(quiet, ctx) < 95.0
    peak = load_module("metrics", "cache_group_peak").read(quiet, ctx)
    assert 5.0 < peak <= 100.0
    for name in ("swa_decode_roofline", "swa_prefill_roofline"):
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


def test_sliding_layers_that_ignore_their_window_are_not_correct(
        tmp_path, monkeypatch):
    """The program's window groups built with no window: their layers attend
    over every cached token (and nothing is released)."""
    from paddle_tpu.serving import group_cache
    from paddle_tpu.serving.paging import PagedKVCache

    class NoWindow(PagedKVCache):
        def __init__(self, *a, window=0, **kw):
            super().__init__(*a, window=0, **kw)

    monkeypatch.setattr(group_cache, "PagedKVCache", NoWindow)
    res, _ctx = run(tmp_path)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


# -- the readers on a hand-built result ---------------------------------------

DIMS = {"layers": 4, "full_layers": 1, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "hidden": 64, "window": 24, "dense_layers": 0,
        "moe_ffn": 32, "top_k": 4}
SHIFT = 1000.0              # the trace's clock minus perf_counter


def quiet_ctx():
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    c.say = lambda _msg: None
    return c


def synthetic(monkeypatch, *, attrs=True):
    """Four decode steps of 10 ms from t = 10 s, 2 running slots holding
    61,000 tokens (a window layer reads 2,048 of them), 4 layers: each step
    launches ``paged_decode_attention`` once a layer (400 us); then one
    prefill of a 256-row tail (``paged_prefill_attention``, 2 ms a layer)."""
    ring, bench, ops, host = [], [], [], []
    for i in range(4):
        t = 10.0 + 0.01 * i
        swa = dict(swa_full_rows=61000 + i, swa_window_rows=2048,
                   swa_context=61000 + i, swa_blocks_used=[900 + i, 130],
                   swa_blocks=[1000, 200]) if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=2, **swa), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 61000, "running": 2}))
        for k in range(4):
            s = t + SHIFT + 0.001 * k
            ops.append((s, s + 4e-4, "paged_decode_attention.%d" % k,
                        "%%paged_decode_attention.%d = bf16[2,8,4,128]"
                        "{3,2,1,0} custom-call()" % k))
    fill = dict(swa_full_rows=256 * 30000, swa_window_rows=256 * 24,
                swa_full_keys=30256, swa_window_keys=279) if attrs else {}
    ring.append(("engine.prefill", 10.05, 10.06, None,
                 dict(bucket=256, **fill), 200))
    for k in range(4):
        s = 10.05 + SHIFT + 0.002 * k
        ops.append((s, s + 2e-3, "paged_prefill_attention.%d" % k,
                    "%%paged_prefill_attention.%d = bf16[4,8,256,128]"
                    "{3,2,1,0} custom-call()" % k))
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
    context = sum(61000 + i for i in range(4))
    rows = (context + 3 * 4 * 2048) / 4             # a layer's mean
    assert read("swa_attended_share") == pytest.approx(100.0 * rows / context)
    assert read("cache_group_peak") == pytest.approx(100.0 * 903 / 1000)
    # 16 events = 4 whole steps of 4 layers; a row's key and value of 2 x 16
    by_bytes = 4 * rows * 2 * 2 * 16 * 2 / 819e9
    assert read("swa_decode_roofline") == pytest.approx(
        100.0 * by_bytes / (16 * 4e-4), rel=1e-6)
    pairs = (256 * 30000 + 3 * 256 * 24) / 4
    by_ops = 4 * 2.0 * pairs * 4 * 16 * 2 / 197e12
    keys = (30256 + 3 * 279) / 4
    by_bytes = 4 * keys * 2 * 2 * 16 * 2 / 819e9
    assert read("swa_prefill_roofline") == pytest.approx(
        100.0 * max(by_ops, by_bytes) / (4 * 2e-3), rel=1e-6)


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
    for name in NEW[:3]:
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    res["trace"] = tr.Trace(
        {"/device:TPU:0": [(1010.0, 1010.001, "fusion.1", "%fusion.1 = ")]},
        res["trace"].host_spans, {})
    for name in NEW[1:3]:
        assert load_module("metrics", name).read(res, c) is None
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
    # that reader charges every layer the whole context
    assert CELL not in by_name["paged_decode_roofline"]["workloads"]
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "ttft_p50_ms"
    cfg = {c["name"]: c for c in m["configs"]}["mellum2-12b-a2.5b-e8"]
    assert cfg["reduced"] == ["num_experts", "max_position_embeddings"]


def test_the_configuration_keeps_every_published_width_and_all_28_layers():
    import json
    import os

    from bench_testlib import ROOT

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2-12b-a2.5b-e8.json")) as f:
        cfg = json.load(f)
    ref = load_module("references", "mellum")
    d = ref.dims(cfg)
    assert (d["layers"], d["full_layers"], d["window"]) == (28, 7, 1024)
    assert (d["hidden"], d["heads"], d["kv_heads"], d["head_dim"],
            d["moe_ffn"], d["vocab"]) == (2304, 32, 4, 128, 896, 98304)
    assert (d["experts"], d["held"], d["top_k"]) == (64, (0, 8), 8)
    assert cfg["published"] == {"num_experts": 64,
                                "max_position_embeddings": 131072}
    shapes = ref.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert 2.43e9 < params < 2.45e9                     # 4.88 GB in bf16
    with open(os.path.join(ROOT, "benchmarks", "mixes", "ide-open.json")) as f:
        eng = json.load(f)["engine"]
    per_block = 16 * 2048
    pools = (eng["num_kv_blocks"] * 7 + eng["num_window_blocks"] * 21) \
        * per_block
    assert 6.0e9 <= pools <= 7.0e9
    assert 10.9e9 <= 2 * params + pools <= 11.9e9


# -- the reference's short cuts -------------------------------------------------

def test_a_window_layers_block_of_keys_is_the_whole_rows():
    """A sliding layer's block of queries given only the keys that can meet
    its windows equals the same block over every key."""
    ref = load_module("references", "mellum")
    rng = np.random.default_rng(4)
    n, T, Hkv, H, D, W = 128, 320, 2, 4, 16, 24
    q = jnp.asarray(rng.normal(size=(n, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, Hkv, D)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for offset in (0, 64, T - n):
            got = ref._blocked_attention(q, k[:offset + n], v[:offset + n],
                                         offset, W)
            want = ref._attend_block(q, offset + jnp.arange(n),
                                     k[:offset + n], v[:offset + n],
                                     jnp.arange(offset + n), W)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6)


def test_a_shared_opening_goes_through_once_and_changes_nothing():
    """Three sequences, two of which open with the same 1,152 tokens: the
    hidden states equal those of each sequence alone."""
    from benchmarks.harness import weights

    ref = load_module("references", "mellum")
    cfg = dict(tiny("tiny_mellum"), num_hidden_layers=2,
               layer_types=["sliding_attention", "full_attention"],
               mlp_layer_types=["sparse"] * 2, max_position_embeddings=2048)
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

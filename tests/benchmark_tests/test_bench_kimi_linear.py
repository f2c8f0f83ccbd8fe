"""The ``kimi_linear`` family (the code that runs Kimi-Linear-48B-A3B) through
the ``serve_resident`` driver at tiny size: a sound run is ``correct`` with
every context's probe hitting the whole context — every piece behind the
snapshot the piece before left — the fp8 control and runs with one piece of
the mathematics altered in the *program* (the forget gate dropped, the write
strength dropped, the shared key part rotated, the recurrence's state kept in
bfloat16) are not; the five per-layer readers the family brings read a CPU
run's spans and a hand-built trace and return ``None`` where the program gives
them nothing; and the reference's own short cut (the shared opening, behind
its latents, its state and its last columns) equals the long way."""
import dataclasses
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

#: at a hidden size of 256 (at the tiny preset's 64 a layer adds less to the
#: residual stream than the token's own embedding).  In bf16, as served, a
#: sound run reads 0.021 / -0.016 on the two numbers, the fp8 control 0.34 /
#: 0.30, a dropped gate 1.35 / 0.80, a dropped beta 0.93 / 0.30.  A rotated
#: key part and a bf16 state move no served token of a bf16 run at this size
#: (the gaps read the served tokens, so they read as the sound run does):
#: those two are held in float32, where a sound run reads 0 / -0.016, the
#: rotation 2.4e-3 and the bf16 state 3.8e-4 on the first
LIMITS = {"served_logit_gap": 0.1, "sampled_topk_gap": 0.1}
F32_LIMITS = {"served_logit_gap": 1e-4, "sampled_topk_gap": 0.0}
WIDER = dict(hidden_size=256, intermediate_size=256, moe_intermediate_size=64)
DOC = 64          # a resident context: four strides of 16
CELL = "kimi-linear-48b-a3b-s13.serving-session-open"
NEW = ("kda_prefill_roofline", "kda_decode_roofline", "kda_mix_device_ms",
       "hybrid_mla_decode_roofline", "state_snapshot_mb_admit")


def resident_mix(limits, each: int = 2) -> dict:
    """``each`` requests from each of four clients, every one a tail of 16-40
    tokens behind one of two 64-token contexts; answers of 8-24 tokens."""
    mix = dict(tiny("tiny_serve_mix"), kind="serve_resident", limits=limits,
               check_requests=3 * each, reference_pad=128,
               resident={"piece_tokens": 32})
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4,
                  "requests_per_client": each},
        prompt_tokens={"median": 84, "sigma": 0.1, "min": 80, "max": 104},
        output_tokens={"median": 14, "sigma": 0.4, "min": 8, "max": 24},
        max_total_tokens=128,
        shared_prefixes={"count": 2, "tokens": DOC, "share": 1.0})
    mix["engine"] = dict(mix["engine"], max_seq=128, min_bucket=16,
                         num_kv_blocks=80, num_state_snapshots=48)
    mix["warmup_buckets"] = [16, 32, 64]
    return mix


#: for the rotation alone: more of a head in its shared key part, and latent
#: attention in two layers of four (its scores are all but flat otherwise)
ROPY = dict(WIDER, qk_nope_head_dim=8, qk_rope_head_dim=32,
            linear_attn_config=dict(
                tiny("tiny_kimi_linear")["linear_attn_config"],
                kda_layers=[1, 3], full_attn_layers=[2, 4]))


def run(tmp_path, dtype="bfloat16", each: int = 2, config=WIDER, **kw):
    limits = LIMITS if dtype == "bfloat16" else F32_LIMITS
    ctx = RunContext(
        config=dict(tiny("tiny_kimi_linear"), torch_dtype=dtype, **config),
        mix=resident_mix(limits, each), limits=limits, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 44, seconds=12.0 * each / 2,
        **kw)
    driver = load_module("drivers", "serve_resident")
    if dtype != "bfloat16":
        import benchmarks.drivers.serve as serve

        ctx.say(f"engine dtype {dtype}")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(serve, "ENGINE_DTYPE", dtype)
            return driver.run(ctx), ctx
    return driver.run(ctx), ctx


def test_the_family_serves_through_the_resident_driver_and_is_correct(
        tmp_path):
    res, ctx = run(tmp_path, control=True)
    assert res["checks"].correct, res["checks"].rows
    assert res["attempted"] == 8 and res["failed"] == 0
    assert {r[0] for r in res["checks"].rows} == {
        "checked_requests", "served_logit_gap", "sampled_topk_gap"}
    f = res["facts"]
    assert f["resident"]["probe_hits"] == [DOC, DOC]
    d = f["dims"]
    assert (d["attn_layers"], d["state_layers"], d["layers"],
            d["dense_layers"], d["taps"]) == (1, 3, 4, 1, 4)
    assert d["held"] == (0, 4) and (d["kda_heads"], d["kda_dim"]) == (2, 16)
    # every request hit its context whole
    assert res["counters"]["prefix_end"]["hit_tokens"] \
        - res["counters"]["prefix_start"]["hit_tokens"] == 8 * DOC
    # the lower precision fails both of the cell's numbers
    assert all(f["control_gaps"][k] > v for k, v in LIMITS.items()), \
        f["control_gaps"]
    # the program's own spans carry what the state group moved: the reader
    # of program counters reads a CPU run
    quiet = dict(res, facts=dict(f, quiet_window=f["window"]))
    got = load_module("metrics", "state_restored_share").prefills(quiet)
    assert len(got) == 8 and all(a["state_row"] > 0 for a in got)
    weight = 3 * (3 * 96 * 2 + 2 * 16 * 16 * 4)         # a slot's state
    assert all(a["state_bytes_restored"] == weight
               and a["state_bytes_snapshotted"]
               == weight * a["state_snapshots_written"]
               and a["kda_chunks"] == 1
               and 16 <= a["kda_tail_tokens"] <= 40 for a in got)
    mb = load_module("metrics", "state_snapshot_mb_admit").read(quiet, ctx)
    assert mb == pytest.approx(sum(a["state_bytes_snapshotted"]
                                   for a in got) / 8e6)
    assert weight / 1e6 <= mb <= 3 * weight / 1e6
    for name in NEW[:4]:
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


def _without_gate(scan, step):
    return (lambda q, k, v, g, *a, **kw: scan(q, k, v, jnp.zeros_like(g), *a,
                                              **kw),
            lambda s, q, k, v, g, *a, **kw: step(s, q, k, v,
                                                 jnp.zeros_like(g), *a, **kw))


def _without_beta(scan, step):
    return (lambda q, k, v, g, b, *a, **kw: scan(q, k, v, g, jnp.ones_like(b),
                                                 *a, **kw),
            lambda s, q, k, v, g, b, *a, **kw: step(s, q, k, v, g,
                                                    jnp.ones_like(b), *a,
                                                    **kw))


def _with_a_bf16_state(scan, step):
    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def scan16(q, k, v, g, b, s0, *a, **kw):
        o, last, kept = scan(q, k, v, g, b, rounded(s0), *a, **kw)
        return o, rounded(last), rounded(kept)

    def step16(s, *a, **kw):
        o, new = step(s, *a, **kw)
        return o, rounded(new)

    return scan16, step16


@pytest.mark.parametrize("broken,dtype", [
    ("gate_dropped", "bfloat16"), ("beta_dropped", "bfloat16"),
    ("k_r_rotated", "float32"), ("state_in_bf16", "float32")])
def test_a_piece_of_the_mathematics_altered_is_not_correct(
        tmp_path, monkeypatch, broken, dtype):
    """The program's KDA layers without their forget gate (``g = 0``: nothing
    decays), without their write strength (``beta = 1``), its latent layers
    rotating the shared key part as DeepSeek-V3's do, or its recurrence kept
    in bfloat16 between tokens: each fails a limit of its run."""
    from paddle_tpu.models import kimi_linear as km

    if broken == "k_r_rotated":
        real = km.DeepseekV3Attention
        monkeypatch.setattr(
            km, "DeepseekV3Attention",
            lambda c: real(dataclasses.replace(c, mla_use_nope=False)))
    else:
        alter = {"gate_dropped": _without_gate, "beta_dropped": _without_beta,
                 "state_in_bf16": _with_a_bf16_state}[broken]
        scan, step = alter(km.kda_chunk_prefill, km.kda_decode_step)
        monkeypatch.setattr(km, "kda_chunk_prefill", scan)
        monkeypatch.setattr(km, "kda_decode_step", step)
    # (a state rounded to bfloat16 moves a logit by a few 1e-4: twice the
    # tokens, so that one of them lies that close to its runner-up)
    res, _ctx = run(tmp_path, dtype=dtype,
                    each=4 if dtype == "float32" else 2,
                    config=ROPY if broken == "k_r_rotated" else WIDER)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


@pytest.mark.parametrize("config", [WIDER, ROPY], ids=["wider", "ropy"])
def test_a_float32_run_is_correct_at_the_float32_limits(tmp_path, config):
    """What the two float32 cases above are held against: the program as it
    is reads under the limits they fail."""
    res, _ctx = run(tmp_path, dtype="float32", each=4, config=config)
    assert res["checks"].correct, res["checks"].rows


# -- the readers on a hand-built result ---------------------------------------

DIMS = {"layers": 4, "attn_layers": 1, "state_layers": 3, "heads": 4,
        "kv_rank": 32, "rope": 8, "kda_heads": 2, "kda_dim": 16,
        "hidden": 64, "dense_layers": 1, "moe_ffn": 32, "top_k": 4}
SHIFT = 1000.0              # the trace's clock minus perf_counter
SLOT_STATE = 2 * 16 * 16 * 4            # one slot's recurrent bytes a layer


def quiet_ctx():
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    c.say = lambda _msg: None
    return c


def synthetic(monkeypatch, *, attrs=True):
    """Four decode steps of 10 ms from t = 10 s, 2 running slots holding
    17,000 tokens, 4 layers of which one is latent attention and three KDA:
    each step launches ``mla_paged_decode`` once (400 us) and
    ``kda_decode_step`` three times (50 us each); before them two tail
    prefills of 100 and 700 real tokens, each launching ``kda_chunk_prefill``
    three times (2 ms each) and writing one and two snapshots."""
    ring, bench, ops, host = [], [], [], []
    for i, (tail, snaps) in enumerate(((100, 1), (700, 2))):
        t = 9.5 + 0.05 * i
        fill = dict(state_row=7, state_snapshots_written=snaps,
                    state_hit_given_up=0, state_bytes_restored=5000,
                    state_bytes_snapshotted=5000 * snaps,
                    kda_tail_tokens=tail, kda_chunks=-(-tail // 64)) \
            if attrs else {}
        ring.append(("engine.prefill", t, t + 0.02, None,
                     dict(bucket=1024, **fill), 50 + i))
        for j in range(3):
            s = t + SHIFT + 0.002 + 0.004 * j
            ops.append((s, s + 2e-3, f"kda_chunk_prefill.{j}",
                        f"%kda_chunk_prefill.{j} = (f32[2,1024,16]"
                        "{2,1,0}) custom-call()"))
    for i in range(4):
        t = 10.0 + 0.01 * i
        state = dict(state_slots=2, state_bytes=2 * 3 * 2 * SLOT_STATE,
                     state_snapshots_used=30, state_snapshots=100) \
            if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=2, **state), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 17000, "running": 2}))
        s = t + SHIFT + 0.001
        ops.append((s, s + 4e-4, "mla_paged_decode.2",
                    "%mla_paged_decode.2 = bf16[2,4,32]{2,1,0} "
                    "custom-call()"))
        for j in range(3):
            s2 = s + 5e-4 + 1e-4 * j
            ops.append((s2, s2 + 5e-5, f"kda_decode_step.{j}",
                        f"%kda_decode_step.{j} = (f32[4,2,8,16]"
                        "{3,2,1,0}) custom-call()"))
    host = [("engine.step", 9.4 + SHIFT, 9.405 + SHIFT, {})] + host + \
        [("engine.step", 10.07 + SHIFT, 10.075 + SHIFT, {})]
    monkeypatch.setattr(ps, "rows", lambda: ring)
    return {"trace": tr.Trace({"/device:TPU:0": sorted(ops)}, host, {}),
            "spans": bench,
            "facts": {"dims": DIMS, "num_slots": 4, "kv_itemsize": 2,
                      "window": [9.0, 11.0], "quiet_window": [9.0, 11.0]}}


def test_the_new_readers_read_a_synthetic_result(monkeypatch):
    res, c = synthetic(monkeypatch), quiet_ctx()
    read = lambda name: load_module("metrics", name).read(res, c)  # noqa
    assert read("state_snapshot_mb_admit") == pytest.approx(0.0075)
    # 12 events = 4 whole steps of 3 KDA layers; two slots' state in and out
    moved = 4 * 2 * 3 * 2 * SLOT_STATE
    assert read("kda_decode_roofline") == pytest.approx(
        100.0 * moved / 819e9 / (12 * 5e-5), rel=1e-6)
    # 6 events = 2 whole prefills of 3 KDA layers: bound by bytes at this size
    kc = load_module("kernel_costs", "kda_chunk_prefill")
    costs = [kc.cost(100, heads=2, dim=16), kc.cost(700, heads=2, dim=16)]
    by_ops = 3 * sum(f for f, _b in costs) / 197e12
    by_bytes = 3 * sum(b for _f, b in costs) / 819e9
    assert by_bytes > by_ops
    assert read("kda_prefill_roofline") == pytest.approx(
        100.0 * by_bytes / (6 * 2e-3), rel=1e-6)
    # 4 events = 4 whole steps of 1 latent layer of 4
    nbytes = 4 * 17000 * (32 + 8) * 2
    assert read("hybrid_mla_decode_roofline") == pytest.approx(
        100.0 * nbytes / 819e9 / (4 * 4e-4), rel=1e-6)
    # (the accepted reader takes the 4 events for one whole step of 4 layers
    # and scales the steps' tokens to the launches seen, which makes up for
    # the three layers that launch nothing: the same reading, by that route)
    assert load_module("metrics", "mla_decode_roofline").read(res, c) \
        == pytest.approx(read("hybrid_mla_decode_roofline"), rel=1e-6)
    # no scope map in a hand-built trace: nothing to join
    assert read("kda_mix_device_ms") is None


def test_the_kernels_costs_count_the_real_sizes():
    scan = load_module("kernel_costs", "kda_chunk_prefill")
    flops, nbytes = scan.cost(770, heads=32, dim=128)
    assert flops == 2.0 * 32 * 770 * 3 * 128 * 128
    assert nbytes == 4.0 * 32 * (770 * 5 * 128 + 2 * 128 * 128)
    step = load_module("kernel_costs", "kda_decode_step")
    moved = 10 * 10 * 2 * 32 * 128 * 128 * 4            # ten slots, ten layers
    assert step.cost(moved) == (6.0 * moved / 8, float(moved))
    import re
    for kc, name in ((scan, "kda_chunk_prefill"), (step, "kda_decode_step")):
        assert any(re.search(p, f"%{name}.7 = (f32[") for p in kc.PATTERNS)
        assert not any(re.search(p, "%fusion.7 = ") for p in kc.PATTERNS)


def test_kda_mix_device_ms_reads_its_scope_off_a_joined_trace():
    """The device time of a whole ``jit_prefill_step`` under the KDA layers'
    scopes: their projections, ``kda.mix``, ``kda.scan`` and ``state.write``,
    and nothing of a latent layer or of another program."""
    from benchmarks.harness import device_scopes as ds

    root = "KimiLinearForCausalLM/model/layers"
    got = {"jit_prefill_step": {"runs": 2, "busy_s": 0.2, "seconds": {
        (f"{root}/0/kda/q_proj", "fwd"): 0.002,
        (f"{root}/0/kda/kda.mix", "fwd"): 0.001,
        (f"{root}/0/kda/kda.scan/kda_chunk_prefill", "fwd"): 0.02,
        (f"{root}/2/kda/state.write", "fwd"): 0.0004,
        (f"{root}/3/self_attn/mla_paged_prefill", "fwd"): 0.01,
        ("model.head", "fwd"): 0.006}},
        "jit_decode_step": {"runs": 1, "busy_s": 0.01, "seconds": {
            (f"{root}/0/kda/kda.step/kda_decode_step", "fwd"): 0.01}}}
    res = {ds._KEY: got}
    reader = load_module("metrics", "kda_mix_device_ms")
    assert reader.read(res, quiet_ctx()) == pytest.approx(
        1e3 * (0.002 + 0.001 + 0.02 + 0.0004) / 2)
    got["jit_prefill_step"]["seconds"] = {("model.head", "fwd"): 0.006}
    assert reader.read(res, quiet_ctx()) is None


def test_the_new_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent commit: no such attribute on a span, another family's
    dims, no such kernel in the trace."""
    res, c = synthetic(monkeypatch, attrs=False), quiet_ctx()
    for name in ("kda_prefill_roofline", "kda_decode_roofline",
                 "kda_mix_device_ms", "state_snapshot_mb_admit"):
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    res["facts"]["dims"] = {"layers": 24, "heads": 16, "kv_heads": 16,
                            "head_dim": 64}
    for name in NEW[:4]:
        assert load_module("metrics", name).read(res, c) is None
    # JoyAI's dims: latent attention in every layer and no ``attn_layers``
    res["facts"]["dims"] = {"layers": 13, "heads": 32, "kv_rank": 512,
                            "rope": 64}
    assert load_module("metrics", "hybrid_mla_decode_roofline").read(res, c) \
        is None
    res = synthetic(monkeypatch)
    res["trace"] = tr.Trace(
        {"/device:TPU:0": [(1010.0, 1010.001, "fusion.1", "%fusion.1 = ")]},
        res["trace"].host_spans, {})
    for name in NEW[:4]:
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    monkeypatch.setattr(ps, "rows", lambda: [])         # a program with no ring
    for name in ("kda_prefill_roofline", "kda_decode_roofline",
                 "state_snapshot_mb_admit"):
        assert load_module("metrics", name).read(res, c) is None


def test_the_manifest_lists_the_cell_where_its_readers_read():
    m = load_manifest()
    by_name = {e["name"]: e for e in m["per_layer"] + m["end_to_end"]}
    for name in NEW + ("ttft_p50_ms",):
        assert CELL in by_name[name]["workloads"], name
    # the lists the accepted tests pin to their own cells, the reader that
    # charges every layer the latent kernel, and the metric the cell does not
    # report
    for name in ("admit_host_ms", "state_restored_share",
                 "state_snapshot_peak", "conv_device_ms",
                 "hybrid_decode_roofline", "cache_group_peak",
                 "mla_decode_roofline", "paged_decode_roofline",
                 "swa_decode_roofline", "tpot_p50_ms"):
        assert CELL not in by_name[name]["workloads"], name
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "ttft_p50_ms"
    assert "setup_s" in by_name and "workloads" not in by_name["setup_s"]
    cfg = {c["name"]: c for c in m["configs"]}["kimi-linear-48b-a3b-s13"]
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "model_max_length"]
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-linear-48b-a3b-s13", "session-open", 1)
    assert ".serve-" not in CELL            # (``admit_host_ms``'s own rule)
    for text in (cfg["why"], cfg["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable()


def test_the_configuration_keeps_every_published_width_and_the_first_stage():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kimi-linear-48b-a3b-s13.json")) as f:
        cfg = json.load(f)
    ref = load_module("references", "kimi_linear")
    d = ref.dims(cfg)
    assert (d["layers"], d["attn_layers"], d["state_layers"],
            d["dense_layers"], d["taps"]) == (13, 3, 10, 1, 4)
    assert [i for i, k in enumerate(d["kinds"]) if k == ref.MLA] == [3, 7, 11]
    assert (d["hidden"], d["heads"], d["kv_rank"], d["nope"], d["rope"],
            d["v"], d["kda_heads"], d["kda_dim"], d["ffn"], d["moe_ffn"],
            d["vocab"]) == (2304, 32, 512, 128, 64, 128, 32, 128, 9216, 1024,
                            163840)
    assert (d["experts"], d["held"], d["top_k"], d["shared"],
            d["route_scale"]) == (256, (0, 16), 8, 1, 2.446)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["model_max_length"]) == (27, 256, 1048576)
    la, pla = cfg["linear_attn_config"], pub["linear_attn_config"]
    assert la["kda_layers"] == [i for i in pla["kda_layers"] if i <= 13]
    assert la["full_attn_layers"] == [i for i in pla["full_attn_layers"]
                                      if i <= 13]
    assert {k: la[k] for k in ("head_dim", "num_heads",
                               "short_conv_kernel_size")} == \
        {k: pla[k] for k in ("head_dim", "num_heads",
                             "short_conv_kernel_size")}
    assert set(cfg["reduced"]) == set(pub)
    for key in ("assumed", "precision", "deployment"):
        assert cfg[key]
    shapes = ref.weight_shapes(cfg)
    params = sum(int(np.prod(s)) for s, _k in shapes.values())
    assert 2.75e9 < params < 2.755e9                    # 5.50 GB in bf16
    with open(os.path.join(ROOT, "benchmarks", "mixes",
                           "session-open.json")) as f:
        mix = json.load(f)
    eng = mix["engine"]
    slot = 10 * (3 * 12288 * 2 + 32 * 128 * 128 * 4)    # a slot's state
    latents = eng["num_kv_blocks"] * 3 * 16 * 640 * 2   # 576 in 640 lanes
    snaps = eng["num_state_snapshots"] * slot
    state = eng["num_slots"] * slot
    assert 21.6e6 < slot < 21.8e6
    assert 9.5e9 <= 2 * params + latents + snaps + state <= 12.5e9
    sp = mix["params"]["shared_prefixes"]
    assert (sp["count"], sp["tokens"], sp["share"]) == (16, 16384, 1.0)
    assert eng["num_kv_blocks"] >= 16 * 1024 + 64 * 288
    assert mix["resident"]["piece_tokens"] == cfg["snapshot_stride"] == 2048
    assert mix["reference_pad"] == mix["params"]["max_total_tokens"] == 20992


def test_the_programs_model_takes_every_seeded_leaf_and_nothing_else():
    """The adapter lays the reference's tree out under the program's keys:
    every leaf of both, shapes alike, the gate's two parameters as the
    reference derives them."""
    from benchmarks.adapters import _load
    from benchmarks.harness import weights

    ref = load_module("references", "kimi_linear")
    adapter = load_module("adapters", "kimi_linear")
    cfg = tiny("tiny_kimi_linear")
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), 3, jnp.float32)
    model = adapter.build_model(cfg)
    n = _load.load(model, adapter, tree, d)
    assert n == sum(int(v.size) for v in tree.values())
    sd = model.state_dict()
    assert "lm_head" in sd and "model.layers.3.self_attn.q_proj" in sd
    assert not any("q_a_proj" in k or "q_b_proj" in k for k in sd)
    a_log, dt = ref.gate_parameters(tree["layers.0.kda.a"],
                                    tree["layers.0.kda.dt"])
    np.testing.assert_array_equal(
        np.asarray(sd["model.layers.0.kda.A_log"]._value()), np.asarray(a_log))
    np.testing.assert_array_equal(
        np.asarray(sd["model.layers.0.kda.dt_bias"]._value()), np.asarray(dt))
    # the decay spans (0.4, 1) and is not all ones: a dropped gate shows
    alpha = np.exp(-np.exp(np.asarray(a_log))[:, None] * np.log1p(np.exp(
        np.asarray(dt).reshape(len(a_log), -1))))
    assert 0.3 < alpha.min() < 0.75 and 0.85 < alpha.max() < 1.0
    # bfloat16 holds the derived values exactly
    for x in (a_log, dt):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(x.astype(jnp.bfloat16).astype(
                jnp.float32)))


def test_a_shared_opening_goes_through_once_and_changes_nothing():
    """Three sequences, two of which open with the same 1,152 tokens: the
    hidden states equal those of each sequence alone (the remainders start
    behind the opening's latents and, in a KDA layer, its state and last
    three columns), and an operator's rows in blocks equal its rows whole."""
    from benchmarks.harness import weights

    ref = load_module("references", "kimi_linear")
    cfg = dict(tiny("tiny_kimi_linear"), model_max_length=2048)
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
        whole = ref.ROW_BLOCK
        try:
            ref.ROW_BLOCK = 4096            # no operator takes blocks
            ref._jit_block.cache_clear()
            for s, got in zip(seqs, many):
                alone = ref.hidden(tree, jnp.asarray(s), d)
                np.testing.assert_allclose(np.asarray(got),
                                           np.asarray(alone), atol=2e-5)
        finally:
            ref.ROW_BLOCK = whole
            ref._jit_block.cache_clear()


def test_the_reference_imports_nothing_of_the_program_and_scans_token_by_token():
    with open(os.path.join(ROOT, "benchmarks", "references",
                           "kimi_linear.py")) as f:
        src = f.read()
    assert "paddle_tpu" not in src.replace("imports nothing of", "")
    assert "import" in src and "pallas" not in src
    assert "jax.lax.scan(step, s0, (q, k, v, g, beta))" in src

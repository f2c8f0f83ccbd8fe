"""The ``deepseek_v3`` family (the code that runs JoyAI-LLM-Flash) through
the serve driver at tiny size in bf16: a sound run is ``correct``, three runs
with one piece of the mathematics left out of the *program* are not (router
bias dropped, ``k_rope`` unrotated, shared expert left out), and the four
per-layer readers the family brings read a hand-built result and return
``None`` where the program gives them nothing."""
import jax.numpy as jnp
import pytest

from bench_testlib import ROOT, tiny
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext
from benchmarks.harness.manifest import load_manifest, load_module

#: the unit tests' preset with a short rotary period and more of a head in
#: its rotary part; in bf16, as served, a sound run reads 2e-4 / under 0 on the
#: two numbers, the fp8 control 0.024 / 0.040, a dropped bias or shared
#: expert 0.05-0.12
NARROW = {"hidden_size": 128, "q_lora_rank": 96, "qk_nope_head_dim": 8,
          "qk_rope_head_dim": 32, "rope_theta": 100.0,
          "max_position_embeddings": 128}
LIMITS = {"served_logit_gap": 0.006, "sampled_topk_gap": 0.006}
#: an unrotated k_rope moves no served token until attention scores stop
#: being all but flat (hidden 128 leaves q.k at a few 1e-2), which takes
#: hidden 512; there a bf16 run's rare routing flip reads as much as the fp8
#: control does (0.25 against 0.10), so this one path is held in float32,
#: where a sound run reads 1e-5 and the unrotated one 0.15-0.2
WIDE = dict(NARROW, hidden_size=512, q_lora_rank=512)
WIDE_LIMITS = {"served_logit_gap": 0.03, "sampled_topk_gap": 0.03}


def closed_mix(limits) -> dict:
    """Twelve requests from four clients, all finished well inside the
    window: the same requests are checked whatever the machine's speed."""
    mix = dict(tiny("tiny_serve_mix"), limits=limits, check_requests=12,
               reference_pad=128)
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4, "requests_per_client": 3},
        prompt_tokens={"median": 60, "sigma": 0.4, "min": 24, "max": 100},
        output_tokens={"median": 14, "sigma": 0.3, "min": 8, "max": 20},
        max_total_tokens=128)
    mix["engine"] = dict(mix["engine"], max_seq=128)
    return mix


def run(tmp_path, sabotage=None, wide=False, **kw):
    limits = WIDE_LIMITS if wide else LIMITS
    ctx = RunContext(
        config=dict(tiny("tiny_deepseek_v3"), **(WIDE if wide else NARROW)),
        mix=closed_mix(limits), limits=limits, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 11, seconds=8.0,
        sabotage=sabotage, **kw)
    serve = load_module("drivers", "serve")
    if wide:
        serve.ENGINE_DTYPE = "float32"      # this copy of the module only
    return serve.run(ctx), ctx


def expert_layers(parts):
    return [lyr for lyr in parts["engine"].model.model.layers if lyr.is_moe]


def drop_router_bias(parts):
    for lyr in expert_layers(parts):
        b = lyr.mlp.e_score_correction_bias
        b._set_data(jnp.zeros_like(b._value()))


def leave_shared_expert_out(parts):
    for lyr in expert_layers(parts):
        w = lyr.mlp.shared_experts.down_proj
        w._set_data(jnp.zeros_like(w._value()))


def test_the_family_serves_through_the_driver_and_is_correct(tmp_path):
    res, ctx = run(tmp_path, control=True)
    assert res["checks"].correct, res["checks"].rows
    assert res["attempted"] == 12 and res["failed"] == 0
    assert {r[0] for r in res["checks"].rows} == {
        "checked_requests", "served_logit_gap", "sampled_topk_gap"}
    assert res["facts"]["dims"]["held"] == (0, 4)
    # the lower precision fails one of the cell's numbers
    assert any(res["facts"]["control_gaps"][k] > v for k, v in LIMITS.items())
    # the program's own decode steps carry what they routed
    moe = load_module("metrics", "moe_experts_touched")
    steps = moe.steps(dict(res, facts=dict(res["facts"], quiet_window=res[
        "facts"]["window"])))
    assert steps and all(0 <= a["moe_experts_touched"]
                         <= a["moe_assignments_held"]
                         <= a["moe_tokens"] * 4 * 2 for a in steps)
    for name in ("mla_decode_roofline", "moe_experts_roofline"):
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


@pytest.mark.parametrize("broken", ["router_bias_dropped", "k_rope_unrotated",
                                    "shared_expert_left_out"])
def test_a_piece_of_the_mathematics_left_out_is_not_correct(
        tmp_path, monkeypatch, broken):
    alter = {"router_bias_dropped": drop_router_bias,
             "shared_expert_left_out": leave_shared_expert_out}.get(broken)
    wide = broken == "k_rope_unrotated"
    if wide:
        # k_rope (the one 3-D argument of ``_rope``) goes into the cache as
        # the projection left it, from the first trace on
        from paddle_tpu.models import deepseek_v3 as dm

        rope = dm._rope
        monkeypatch.setattr(
            dm, "_rope",
            lambda x, pos, theta: x if x.ndim == 3 else rope(x, pos, theta))
    res, _ctx = run(tmp_path, sabotage=alter, wide=wide)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


# -- the four readers on a hand-built result ---------------------------------

DIMS = {"layers": 3, "dense_layers": 1, "heads": 4, "kv_rank": 32, "rope": 8,
        "hidden": 64, "moe_ffn": 32, "top_k": 4}
SHIFT = 1000.0              # the trace's clock minus perf_counter


def quiet_ctx():
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    c.say = lambda _msg: None
    return c


def synthetic(monkeypatch, *, attrs=True):
    """Four decode steps of 10 ms from t = 10 s, 3 running slots, 2 expert
    layers; each step launches the decode kernel 3 times (100 us each) and
    the grouped kernel 4 times at ``slots * top_k`` = 16 rows (50 us each),
    and once at a prefill's 64 rows."""
    ring, bench, ops, host = [], [], [], []
    for i in range(4):
        t = 10.0 + 0.01 * i
        moe = dict(moe_tokens=3, moe_assignments_held=5 + i,
                   moe_experts_touched=4) if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=3, **moe), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 3000, "running": 3}))
        for k in range(3):
            s = t + SHIFT + 0.001 * k
            ops.append((s, s + 1e-4, "mla_paged_decode.%d" % k,
                        "%%mla_paged_decode.%d = bf16[4,4,32] custom-call()"
                        % k))
        for k in range(4):
            s = t + SHIFT + 0.004 + 0.0005 * k
            ops.append((s, s + 5e-5, "moe_grouped_matmul.%d" % k,
                        "%%moe_grouped_matmul.%d = bf16[16,64]{1,0} "
                        "custom-call()" % k))
    ops.append((10.05 + SHIFT, 10.051 + SHIFT, "moe_grouped_matmul.9",
                "%moe_grouped_matmul.9 = bf16[64,64]{1,0} custom-call()"))
    # a step before and one after, so that the four are interior
    host = [("engine.step", 9.98 + SHIFT, 9.985 + SHIFT, {})] + host + \
        [("engine.step", 10.06 + SHIFT, 10.065 + SHIFT, {})]
    monkeypatch.setattr(ps, "rows", lambda: ring)
    return {"trace": tr.Trace({"/device:TPU:0": sorted(ops)}, host, {}),
            "spans": bench,
            "facts": {"dims": DIMS, "num_slots": 4, "kv_itemsize": 2,
                      "window": [9.0, 11.0], "quiet_window": [9.0, 11.0]}}


def test_the_new_readers_read_a_synthetic_result(monkeypatch):
    res, c = synthetic(monkeypatch), quiet_ctx()
    read = lambda name: load_module("metrics", name).read(res, c)  # noqa
    assert read("moe_experts_touched") == pytest.approx(4 * 4 / (4 * 2))
    assert read("moe_held_share") == pytest.approx(
        100.0 * (5 + 6 + 7 + 8) / (12 * 4 * 2))
    # 12 kernel events = 4 whole steps of 3 layers; 3000 tokens x 40 numbers
    # x 2 bytes x 3 layers a step over 819 GB/s, against 12 x 100 us
    by_bytes = 4 * 3 * 3000 * 40 * 2 / 819e9
    assert read("mla_decode_roofline") == pytest.approx(
        100.0 * by_bytes / 12e-4, rel=1e-6)
    kc = load_module("kernel_costs", "moe_grouped_matmul")
    nbytes = sum(2 * kc.cost((5 + i) / 2, 4 / 2, hidden=64, ffn=32)[1]
                 for i in range(4))
    assert read("moe_experts_roofline") == pytest.approx(
        100.0 * (nbytes / 819e9) / (16 * 5e-5), rel=1e-6)  # not the 64-row one


def test_the_new_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent commit: no such kernel in the trace, no such attribute on
    a span, another family's dims."""
    res, c = synthetic(monkeypatch, attrs=False), quiet_ctx()
    res["trace"] = tr.Trace(
        {"/device:TPU:0": [(1010.0, 1010.001, "fusion.1", "%fusion.1 = ")]},
        res["trace"].host_spans, {})
    for name in ("mla_decode_roofline", "moe_experts_roofline",
                 "moe_experts_touched", "moe_held_share"):
        assert load_module("metrics", name).read(res, c) is None
    res["facts"]["dims"] = {"layers": 24, "heads": 16, "kv_heads": 16,
                            "head_dim": 64}
    for name in ("mla_decode_roofline", "moe_experts_roofline",
                 "moe_experts_touched", "moe_held_share"):
        assert load_module("metrics", name).read(res, c) is None


def test_every_one_line_text_of_the_manifest_is_within_its_200_characters():
    """``test_bench_manifest.py`` holds a cell's ``why`` to its length and
    not a configuration's; the driver refused this configuration's over it."""
    m = load_manifest(ROOT)
    texts = [(e["name"], k, e[k]) for e in m["configs"] + m["workloads"]
             for k in ("why", "source") if k in e]
    texts += [(e["name"], "layer", e["layer"]) for e in m["per_layer"]]
    for name, key, text in texts:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, key)

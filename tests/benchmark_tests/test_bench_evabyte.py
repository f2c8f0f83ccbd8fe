"""The ``evabyte`` family (the code that runs EvaByte) through the
``serve_resident`` driver at tiny size in bf16: a sound run is ``correct``
with every document's probe hitting the whole document by both kinds of hit,
the fp8 control and a run whose summaries are another window's are not, the three per-layer readers the family brings read a hand-built result
and return ``None`` where the program gives them nothing, and the reference's
own short cuts (a window's part at a time, the shared opening) equal the
long way."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import tiny
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext
from benchmarks.harness.manifest import load_module

#: bf16, as served (readings in the test below)
LIMITS = {"served_logit_gap": 0.009, "sampled_topk_gap": 0.006}
DOC = 88          # a document: 2 whole windows of 32 and 3 blocks of 8


def resident_mix() -> dict:
    """Twelve requests from four clients, each a question of 8-24 tokens
    behind one of two 88-token documents; answers of 8-20 tokens, so some
    cross the window's end at 96 inside their prefill and some while
    decoding."""
    mix = dict(tiny("tiny_serve_mix"), kind="serve_resident", limits=LIMITS,
               check_requests=12, reference_pad=128,
               resident={"piece_tokens": 32})
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4, "requests_per_client": 3},
        prompt_tokens={"median": DOC + 6, "sigma": 0.15, "min": DOC + 2,
                       "max": DOC + 24},
        output_tokens={"median": 14, "sigma": 0.3, "min": 8, "max": 20},
        max_total_tokens=128,
        shared_prefixes={"count": 2, "tokens": DOC, "share": 1.0})
    mix["params"]["shared_prefixes"]["tokens"] = DOC
    mix["engine"] = dict(mix["engine"], max_seq=128, min_bucket=8,
                         num_kv_blocks=80, num_summary_blocks=16)
    mix["warmup_buckets"] = [8, 16, 32]
    return mix


def run(tmp_path, sabotage=None, **kw):
    ctx = RunContext(
        config=dict(tiny("tiny_evabyte"), torch_dtype="bfloat16"),
        mix=resident_mix(), limits=LIMITS, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 34, seconds=8.0,
        sabotage=sabotage, **kw)
    return load_module("drivers", "serve_resident").run(ctx), ctx


def test_the_family_serves_through_the_resident_driver_and_is_correct(
        tmp_path):
    res, ctx = run(tmp_path, control=True)
    assert res["checks"].correct, res["checks"].rows
    assert res["attempted"] == 12 and res["failed"] == 0
    assert {r[0] for r in res["checks"].rows} == {
        "checked_requests", "served_logit_gap", "sampled_topk_gap"}
    f = res["facts"]
    assert f["resident"]["probe_hits"] == [DOC, DOC]
    assert f["dims"]["window"] == 32 and f["dims"]["chunk"] == 4
    # every request of the window hit its document: 2 windows by their
    # summaries and 3 blocks by their exact keys
    assert res["counters"]["prefix_end"]["hit_tokens"] \
        - res["counters"]["prefix_start"]["hit_tokens"] == 12 * DOC
    # the lower precision fails one of the cell's numbers
    assert any(f["control_gaps"][k] > v for k, v in LIMITS.items()), \
        f["control_gaps"]
    # the program's own decode steps carry the rows they attended to
    share = load_module("metrics", "eva_attended_share")
    quiet = dict(res, facts=dict(f, quiet_window=f["window"]))
    steps = share.steps(quiet)
    assert steps and all(
        0 < a["eva_exact_rows"] + a["eva_summary_rows"] < a["eva_context"]
        for a in steps)
    assert 25.0 < share.read(quiet, ctx) < 60.0
    for name in ("eva_decode_roofline", "eva_prefill_roofline"):
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


def test_summaries_of_the_wrong_window_are_not_correct(tmp_path, monkeypatch):
    """The program's publishing reads window 0's rows whatever window
    closed: every later window's summaries are another window's."""
    from paddle_tpu.ops.pallas import eva_attention_kernel as eva

    sound = eva.window_rows
    monkeypatch.setattr(
        eva, "window_rows",
        lambda pool, row, window_idx, **kw: sound(pool, row,
                                                  window_idx * 0, **kw))
    res, _ctx = run(tmp_path)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


# -- the three readers on a hand-built result ---------------------------------

DIMS = {"layers": 3, "heads": 4, "kv_heads": 4, "head_dim": 16, "hidden": 64,
        "window": 32, "chunk": 4}
SHIFT = 1000.0              # the trace's clock minus perf_counter


def quiet_ctx():
    c = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                   trace=True, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    c.say = lambda _msg: None
    return c


def synthetic(monkeypatch, *, attrs=True):
    """Four decode steps of 10 ms from t = 10 s, 2 running slots holding
    57,000 tokens and attending to 3,500 exact and 3,328 summary rows, 3
    layers: each step launches ``eva_paged_decode`` (400 us) once a layer;
    then one prefill of a 512-row tail (``eva_paged_prefill``, 2 ms a
    layer)."""
    ring, bench, ops, host = [], [], [], []
    for i in range(4):
        t = 10.0 + 0.01 * i
        eva = dict(eva_exact_rows=3500, eva_summary_rows=3328,
                   eva_context=57000 + i,
                   eva_windows_published=0) if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=2, **eva), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 57000, "running": 2}))
        for k in range(3):
            s = t + SHIFT + 0.001 * k
            ops.append((s, s + 4e-4, "eva_paged_decode.%d" % k,
                        "%%eva_paged_decode.%d = bf16[16,1,32,128]{3,2,1,0} "
                        "custom-call()" % k))
    pre = dict(bucket=512, eva_windows=1, eva_windows_published=0,
               eva_rows=1_700_000, eva_keys=3700) if attrs else dict(
                   bucket=512)
    ring.append(("engine.prefill", 10.045, 10.052, None, pre, 200))
    for k in range(3):
        s = 10.045 + SHIFT + 0.002 * k
        ops.append((s, s + 2e-3, "eva_paged_prefill.%d" % k,
                    "%%eva_paged_prefill.%d = bf16[32,1,512,128]{3,2,1,0} "
                    "custom-call()" % k))
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
    context = sum(57000 + i for i in range(4))
    assert read("eva_attended_share") == pytest.approx(
        100.0 * 4 * (3500 + 3328) / context)
    # 12 events = 4 whole steps of 3 layers; a row's key and value are read
    by_bytes = 3 * 4 * (3500 + 3328) * 2 * 4 * 16 * 2 / 819e9
    assert read("eva_decode_roofline") == pytest.approx(
        100.0 * by_bytes / (12 * 4e-4), rel=1e-6)
    # 3 events = one prefill of 3 layers; bound by operations
    by_ops = 3 * 1_700_000 * 4 * 4 * 16 / 197e12
    by_bytes = 3 * 3700 * 2 * 4 * 16 * 2 / 819e9
    assert by_ops > by_bytes
    assert read("eva_prefill_roofline") == pytest.approx(
        100.0 * by_ops / (3 * 2e-3), rel=1e-6)


def test_the_new_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent commit: no such kernel in the trace, no such attribute on
    a span, another family's dims."""
    names = ("eva_attended_share", "eva_decode_roofline",
             "eva_prefill_roofline")
    res, c = synthetic(monkeypatch, attrs=False), quiet_ctx()
    for name in names:
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    res["trace"] = tr.Trace(
        {"/device:TPU:0": [(1010.0, 1010.001, "fusion.1", "%fusion.1 = ")]},
        res["trace"].host_spans, {})
    for name in names[1:]:
        assert load_module("metrics", name).read(res, c) is None
    res = synthetic(monkeypatch)
    res["facts"]["dims"] = {"layers": 24, "heads": 16, "kv_heads": 16,
                            "head_dim": 64}
    for name in names[1:]:
        assert load_module("metrics", name).read(res, c) is None


# -- the reference's short cuts ------------------------------------------------

def plain_forward(ref, tree, tokens, d):
    """The layer equations the long way: the whole sequence at once, every
    chunk's summary, one masked softmax a head."""
    W, C, H, D = d["window"], d["chunk"], d["heads"], d["head_dim"]
    n = len(tokens)
    x = tree["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    pos = jnp.arange(n)
    for i in range(d["layers"]):
        lw = ref.layer_weights(tree, i, d)
        a = ref._rms(x, lw["input_norm.g"], d["eps"])
        q = ref._rope((a @ lw["attn.wq"]).reshape(n, H, D), pos, d["theta"])
        k = ref._rope((a @ lw["attn.wk"]).reshape(n, H, D), pos, d["theta"])
        v = (a @ lw["attn.wv"]).reshape(n, H, D)
        whole = n // W * W
        ks, vs = ref.summaries(k[:whole], v[:whole],
                               ref.pooling_vector(lw["attn.phi"]),
                               ref.pooling_vector(lw["attn.mu"]), C)
        ok_ex = (pos[None] // W == pos[:, None] // W) & (pos[None] <= pos[:, None])
        ok_su = (jnp.arange(whole // C) * C // W)[None] < pos[:, None] // W
        s = jnp.concatenate([
            jnp.where(ok_su[None], jnp.einsum("qhd,khd->hqk", q, ks),
                      -jnp.inf),
            jnp.where(ok_ex[None], jnp.einsum("qhd,khd->hqk", q, k),
                      -jnp.inf)], -1) * D ** -0.5
        p = jax.nn.softmax(s, -1)
        r = whole // C
        o = jnp.einsum("hqk,khd->qhd", p[..., :r], vs) \
            + jnp.einsum("hqk,khd->qhd", p[..., r:], v)
        x = x + o.reshape(n, -1) @ lw["attn.wo"]
        u = ref._rms(x, lw["post_norm.g"], d["eps"])
        x = x + (jax.nn.silu(u @ lw["mlp.w_gate"]) * (u @ lw["mlp.w_up"])
                 ) @ lw["mlp.w_down"]
    return x


def test_a_windows_part_at_a_time_and_a_shared_opening_change_nothing():
    """Three sequences, two of which open with the same 1,088 tokens (34
    windows): the hidden states equal those of each sequence alone, and
    those equal the whole sequence at once."""
    from benchmarks.harness import weights

    ref = load_module("references", "evabyte")
    cfg = dict(tiny("tiny_evabyte"), num_hidden_layers=2,
               max_position_embeddings=2048)
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), 5, jnp.float32)
    rng = np.random.default_rng(6)
    doc = rng.integers(0, 64, (1100,), dtype=np.int32)
    seqs = [np.concatenate([doc, rng.integers(0, 64, (116,), np.int32)]),
            rng.integers(0, 64, (1216,), dtype=np.int32),
            np.concatenate([doc, rng.integers(0, 64, (116,), np.int32)])]
    assert ref.shared_openings(seqs) == [(1088, [0, 2]), (0, [1])]
    assert ref.pieces(40, 100, 32) == [(40, 64), (64, 96), (96, 100)]
    with jax.default_matmul_precision("highest"):
        many = ref.hidden_many(lambda names: {n: tree[n] for n in names},
                               [jnp.asarray(s) for s in seqs], d)
        for s, got in zip(seqs, many):
            alone = ref.hidden(tree, jnp.asarray(s), d)
            np.testing.assert_allclose(np.asarray(got), np.asarray(alone),
                                       atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(many[1]),
            np.asarray(plain_forward(ref, tree, seqs[1], d)), atol=5e-5)

"""The ``keye_vl2`` family (the code that runs Keye-VL-2.0-30B-A3B's language
model) through the ``serve_resident`` driver at tiny size in bf16: a sound run
is ``correct`` with every document's probe hitting the whole document, the
fp8 control and a run whose selection is replaced by the first ``topk``
tokens are not, the three per-layer readers the family brings read a
hand-built result and return ``None`` where the program gives them nothing,
and the reference's own short cuts (the searched cut, the shared opening)
equal the long way."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import ROOT, tiny
from benchmarks.harness import program_spans as ps
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness.context import RunContext
from benchmarks.harness.manifest import load_manifest, load_module

#: bf16, as served: a sound run reads 0.038 / 0.002 on the two numbers, the
#: fp8 control 0.102 / 0.126, the first-``topk`` selection 0.41 / 0.28
LIMITS = {"served_logit_gap": 0.065, "sampled_topk_gap": 0.065}
DOC = 64                  # a document's tokens: past topk (24) before a question


def resident_mix() -> dict:
    """Twelve requests from four clients, each a question of 8-24 tokens
    behind one of two 64-token documents, all finished well inside the
    window."""
    mix = dict(tiny("tiny_serve_mix"), kind="serve_resident", limits=LIMITS,
               check_requests=12, reference_pad=128,
               resident={"piece_tokens": 32})
    mix["params"] = dict(
        mix["params"],
        arrivals={"kind": "closed", "clients": 4, "requests_per_client": 3},
        prompt_tokens={"median": DOC + 16, "sigma": 0.1, "min": DOC + 8,
                       "max": DOC + 24},
        output_tokens={"median": 14, "sigma": 0.3, "min": 8, "max": 20},
        max_total_tokens=128,
        shared_prefixes={"count": 2, "tokens": DOC, "share": 1.0})
    mix["engine"] = dict(mix["engine"], max_seq=128, min_bucket=32)
    mix["warmup_buckets"] = [32]
    return mix


def run(tmp_path, sabotage=None, **kw):
    ctx = RunContext(
        config=dict(tiny("tiny_keye_vl2"), torch_dtype="bfloat16"),
        mix=resident_mix(), limits=LIMITS, trace=False,
        out_dir=str(tmp_path), seed=2 ** 31 + 30, seconds=8.0,
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
    assert f["dims"]["held"] == (0, 4) and f["dims"]["topk"] == 24
    # every request of the window hit its document and took one bucket
    assert res["counters"]["prefix_end"]["hit_tokens"] \
        - res["counters"]["prefix_start"]["hit_tokens"] == 12 * DOC
    # the lower precision fails one of the cell's numbers
    assert any(f["control_gaps"][k] > v for k, v in LIMITS.items())
    # the program's own decode steps carry what they selected
    share = load_module("metrics", "dsa_selected_share")
    quiet = dict(res, facts=dict(f, quiet_window=f["window"]))
    steps = share.steps(quiet)
    assert steps and all(0 < a["dsa_selected"] < a["dsa_context"]
                         for a in steps)
    assert 20.0 < share.read(quiet, ctx) < 45.0      # ~24 of 72-100 tokens
    for name in ("dsa_index_roofline", "dsa_decode_roofline"):
        assert load_module("metrics", name).read(res, ctx) is None  # no trace


def test_a_selection_of_the_first_topk_tokens_is_not_correct(tmp_path,
                                                            monkeypatch):
    """The program's index scores replaced by ``-position``: every query
    keeps the first ``topk`` tokens of its context, whatever they hold."""
    from paddle_tpu.ops.pallas import dsa_attention_kernel as dsa

    def first(q_idx, w, pool, tables, *_a, heads, **_kw):
        G, rows = q_idx.shape[0], q_idx.shape[1] // heads
        T = tables.shape[1] * pool.shape[1]
        return jnp.broadcast_to(-jnp.arange(T, dtype=jnp.float32),
                                (G, rows, T))

    monkeypatch.setattr(dsa, "index_scores", first)
    monkeypatch.setattr(dsa, "index_scores_reference", first)
    res, _ctx = run(tmp_path)
    failed = [r[0] for r in res["checks"].rows if not r[3]]
    assert failed and set(failed) <= set(LIMITS), res["checks"].rows
    assert not res["checks"].correct


# -- the three readers on a hand-built result ---------------------------------

DIMS = {"layers": 3, "dense_layers": 0, "heads": 4, "kv_heads": 2,
        "head_dim": 16, "idx_heads": 4, "idx_dim": 8, "hidden": 64,
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
    60,000 cached tokens of which 4,096 selected, 3 layers: each step
    launches ``dsa_index_scores`` (200 us) and ``dsa_sparse_decode`` (400 us)
    once a layer; one prefill-time ``dsa_index_scores`` call (a group's 32
    rows) lies among them."""
    ring, bench, ops, host = [], [], [], []
    for i in range(4):
        t = 10.0 + 0.01 * i
        dsa = dict(dsa_selected=4096, dsa_context=60000 + i) if attrs else {}
        ring.append(("engine.step", t, t + 0.009, None,
                     dict(step=i, admitted=0, running=2, **dsa), 100 + i))
        dur = 0.0090 + 0.0001 * i        # distinct: the clocks are matched
        bench.append(("engine.step", t - 1e-5, t - 1e-5 + dur, {}))
        host.append(("engine.step", t - 1e-5 + SHIFT, t - 1e-5 + dur + SHIFT,
                     {"kv_tokens": 60000, "running": 2}))
        for k in range(3):
            s = t + SHIFT + 0.001 * k
            ops.append((s, s + 2e-4, "dsa_index_scores.%d" % k,
                        "%%dsa_index_scores.%d = f32[4,1,32768]{2,1,0} "
                        "custom-call()" % k))
            ops.append((s + 3e-4, s + 7e-4, "dsa_sparse_decode.%d" % k,
                        "%%dsa_sparse_decode.%d = bf16[4,4,128]{2,1,0} "
                        "custom-call()" % k))
    ops.append((10.05 + SHIFT, 10.051 + SHIFT, "dsa_index_scores.9",
                "%dsa_index_scores.9 = f32[16,32,32768]{2,1,0} "
                "custom-call()"))
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
    context = sum(60000 + i for i in range(4))
    assert read("dsa_selected_share") == pytest.approx(
        100.0 * 4 * 4096 / context)
    # 12 events = 4 whole steps of 3 layers; a key is read at its stored
    # 128 lanes x 2 bytes, and 4 bytes of score are written for it
    by_bytes = 3 * context * (128 * 2 + 4) / 819e9
    assert read("dsa_index_roofline") == pytest.approx(
        100.0 * by_bytes / (12 * 2e-4), rel=1e-6)      # not the 32-row call
    by_bytes = 3 * 4 * 4096 * 2 * 2 * 16 * 2 / 819e9
    assert read("dsa_decode_roofline") == pytest.approx(
        100.0 * by_bytes / (12 * 4e-4), rel=1e-6)


def test_the_new_readers_read_nothing_from_a_program_without_them(
        monkeypatch):
    """The parent commit: no such kernel in the trace, no such attribute on
    a span, another family's dims."""
    names = ("dsa_selected_share", "dsa_index_roofline",
             "dsa_decode_roofline")
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

def test_the_references_searched_cut_is_the_sorts():
    ref = load_module("references", "keye_vl2")
    rng = np.random.default_rng(4)
    z = rng.normal(size=(6, 300)).astype(np.float32)
    z[0, :200] = -np.inf                      # a row of 100 entries
    z[1, 50:90] = z[1, 7]                     # ties
    z[2] = 0.0
    z[3, ::2] = -0.0
    for k in (1, 24, 100, 300, 2048):
        got = np.asarray(ref.kth_largest(jnp.asarray(z), k))
        want = np.sort(z, axis=1)[:, ::-1][:, min(k, 300) - 1]
        np.testing.assert_array_equal(got, want + 0.0)


def test_a_shared_opening_goes_through_once_and_changes_nothing():
    """Three sequences, two of which open with the same 1,152 tokens: the
    hidden states equal those of each sequence alone."""
    import jax

    from benchmarks.harness import weights

    ref = load_module("references", "keye_vl2")
    cfg = dict(tiny("tiny_keye_vl2"), num_hidden_layers=1,
               max_position_embeddings=2048)
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

"""Both drivers at ``gpt_tiny`` / ``llama_tiny`` size through the code the
chip runs, the result line's keys, the refusal of a CPU, and the two ways a
run has to come out as not correct: the timed path broken underneath, and
the lower-precision control put in the program's place."""
import io
import json
import contextlib

import pytest

from bench_testlib import ROOT, context, tiny
from benchmarks.harness import manifest as mf
from benchmarks.harness.manifest import load_module


@pytest.fixture(autouse=True)
def _no_global_mesh_left_behind():
    """The train driver calls ``fleet.init``, which sets the process-wide
    mesh; a later test file in the same worker must not inherit it."""
    yield
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.set_global_mesh(None)


@pytest.fixture(scope="module")
def train_result(tmp_path_factory):
    ctx = context("gpt", tiny("tiny_train_mix"),
                  tmp_path_factory.mktemp("train"), control=True)
    return load_module("drivers", "train").run(ctx), ctx


@pytest.fixture(scope="module")
def serve_result(tmp_path_factory):
    ctx = context("gpt", tiny("tiny_serve_mix"),
                  tmp_path_factory.mktemp("serve"), seconds=2.5, control=True)
    return load_module("drivers", "serve").run(ctx), ctx


def _closed_mix():
    mix = tiny("tiny_serve_mix")
    mix["params"]["arrivals"] = {"kind": "closed", "clients": 4,
                                 "requests_per_client": 40}
    del mix["params"]["sampled"]
    return mix


def test_train_driver_runs_and_is_correct(train_result):
    res, _ctx = train_result
    assert res["checks"].correct, res["checks"].rows
    assert {r[0] for r in res["checks"].rows} == {
        "loss_rel", "grad_norm_rel", "grad_dir_rel", "delta_norm_rel",
        "loss_last_over_first"}
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert set(res["end_to_end"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert res["end_to_end"]["train_tokens_per_s_chip"] > 0
    assert res["facts"]["compiles_in_window"] == 0
    # set-up does not count the reference's time
    assert res["end_to_end"]["setup_s"] > 0


def test_train_metric_readers_read_what_a_cpu_run_has(train_result):
    res, ctx = train_result
    # no chip: no peaks, no trace -> every reader leaves its metric out
    for name in ("train_mfu", "flash_roofline", "train_step_device_ms",
                 "device_idle.train", "device_idle"):
        assert load_module("metrics", name).read(res, ctx) is None
    ctx2 = context("gpt", ctx.mix, ctx.out_dir,
                   peaks={"bf16_flops_per_s": 1e12})
    assert res["memory_peak_bytes"] >= 0
    # the program's state was freed before the reference ran
    assert res["facts"]["live_bytes_after_free"] < 1e6
    mfu = load_module("metrics", "train_mfu").read(res, ctx2)
    assert 0 < mfu < 100


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    def freeze(parts):
        import paddle_tpu as paddle

        model = parts["model"]

        @paddle.jit.to_static
        def loss_only(x, y):
            with paddle.amp.auto_cast(dtype="bfloat16", level="O2"):
                return model.compute_loss(x, y)

        parts["train_step"] = loss_only

    ctx = context("gpt", tiny("tiny_train_mix"), tmp_path, sabotage=freeze)
    res = load_module("drivers", "train").run(ctx)
    assert not res["checks"].correct
    failed = {r[0] for r in res["checks"].rows if not r[3]}
    assert {"grad_norm_rel", "grad_dir_rel", "delta_norm_rel"} <= failed


def test_serve_driver_open_loop_runs_and_is_correct(serve_result):
    res, _ctx = serve_result
    assert res["checks"].correct, res["checks"].rows
    assert res["attempted"] > 5 and res["failed"] == 0
    assert set(res["end_to_end"]) == {"setup_s", "serve_tokens_per_s",
                                      "itl_p99_ms", "itl_mean_ms",
                                      "itl_p50_ms", "tpot_p50_ms",
                                      "ttft_p50_ms", "ttft_mean_ms",
                                      "ttft_p95_ms"}
    assert {r[0] for r in res["checks"].rows} == {
        "checked_requests", "served_logit_gap", "sampled_topk_gap"}
    load = res["facts"]["load"]
    assert load["in_engine_max"] >= 1 and load["pool_blocks_peak"] >= 1
    assert res["end_to_end"]["ttft_p95_ms"] >= res["end_to_end"][
        "ttft_p50_ms"] > 0
    assert res["facts"]["compiles_in_window"] == 0
    assert res["facts"]["checked_tokens"] > 0
    assert res["memory_peak_bytes"] >= 0


def test_serve_metric_readers(serve_result):
    res, ctx = serve_result
    read = lambda name: load_module("metrics", name).read(res, ctx)  # noqa
    assert 0 < read("decode_step_ms") < 5000
    assert 0 < read("prefill_stall_share") < 100
    assert 0 < read("slot_occupancy") <= 100
    assert 0 < read("prefix_hit_tokens") < 100       # half share a prefix
    assert read("ttft_p95_ms") == res["end_to_end"]["ttft_p95_ms"]
    assert read("itl_p99_ms") == res["end_to_end"]["itl_p99_ms"]
    assert read("gap_mean_ms") == res["end_to_end"]["itl_mean_ms"]
    assert read("paged_decode_roofline") is None     # needs a device trace
    assert read("device_idle.serve") is None


def test_serve_driver_closed_loop_llama(tmp_path):
    ctx = context("llama", _closed_mix(), tmp_path, seconds=2.0)
    res = load_module("drivers", "serve").run(ctx)
    assert res["checks"].correct, res["checks"].rows
    assert "ttft_mean_ms" not in res["end_to_end"]
    assert res["end_to_end"]["serve_tokens_per_s"] > 0
    occ = load_module("metrics", "slot_occupancy").read(res, ctx)
    assert occ > 50                                  # four clients, four slots


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path):
    def alter(parts):
        eng = parts["engine"]
        emit = eng._emit_token

        def wrong(req, tok, now):
            return emit(req, (int(tok) + 1) % eng.config.vocab_size, now)

        eng._emit_token = wrong

    ctx = context("gpt", tiny("tiny_serve_mix"), tmp_path, seconds=2.0,
                  sabotage=alter)
    res = load_module("drivers", "serve").run(ctx)
    assert not res["checks"].correct
    assert [r[0] for r in res["checks"].rows if not r[3]] == \
        ["served_logit_gap", "sampled_topk_gap"]


def test_too_few_finished_requests_to_check_is_not_correct(tmp_path):
    mix = tiny("tiny_serve_mix")
    mix["check_requests"] = 400         # more than a window can finish
    ctx = context("gpt", mix, tmp_path, seconds=1.0)
    res = load_module("drivers", "serve").run(ctx)
    assert [r[0] for r in res["checks"].rows if not r[3]] == \
        ["checked_requests"]


def test_the_lower_precision_control_fails_where_the_program_passes(
        train_result, serve_result):
    """The control is the reference computed in fp8 in the program's place.
    At tiny size the limits are the tiny mixes' own; the cells' limits and the
    readings they were set from (chip runs) are in PERF.md."""
    res, ctx = serve_result
    for name, limit in ctx.limits.items():
        assert res["facts"]["gaps"][name] <= limit
        assert res["facts"]["control_gaps"][name] is not None
    tres, tctx = train_result
    control = dict(zip(("loss_rel", "grad_norm_rel", "grad_dir_rel",
                        "delta_norm_rel"), tres["facts"]["control"]))
    program = dict(zip(control, tres["facts"]["program_numbers"]))
    lim = tctx.limits
    assert all(program[k] <= lim[k] for k in lim)
    # the lower precision fails one of the cell's numbers, not each
    assert control["grad_dir_rel"] > lim["grad_dir_rel"]


def test_result_line_has_exactly_the_contracts_keys(monkeypatch, tmp_path,
                                                    serve_result):
    """``run.main`` with the chip gate stepped over: the rest of a run, down
    to the printed line."""
    import benchmarks.run as run
    from benchmarks.harness import peaks as pk

    res, _ctx = serve_result
    monkeypatch.setattr(pk, "attached", lambda chips: (
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        pk.PEAKS["TPU v5 lite"]))
    monkeypatch.setattr(pk, "memory_peak_bytes", lambda: 123)
    monkeypatch.setattr(run, "place_compile_cache", lambda: "off")
    monkeypatch.setattr(run.mf, "load_module", lambda kind, name: (
        type("D", (), {"run": staticmethod(lambda ctx: res)})
        if kind == "drivers" else mf.load_module(kind, name)))
    cell = next(w["name"] for w in mf.load_manifest(ROOT)["workloads"]
                if w["traffic"] == "chat-open")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", "5", "--seconds", "1",
                       "--trace", "0"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert "setup_s" in line["metrics"]


def test_the_command_refuses_a_cpu_and_prints_no_result(capsys):
    import benchmarks.run as run

    cell = mf.load_manifest(ROOT)["workloads"][0]["name"]
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out

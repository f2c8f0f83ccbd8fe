"""The trace reduction and the kernel cost functions against hand-worked
numbers, on a hand-built trace."""
import pytest

import bench_testlib  # noqa: F401
from benchmarks.harness import stats, trace_reduce as tr
from benchmarks.harness.manifest import load_module

# one device: busy 0-2, 1-3 (overlap), 5-6, 8-9; host spans name the gaps
TRACE = tr.Trace(
    device_ops={"/device:TPU:0": [
        (0.0, 2.0, "fusion.1", "fusion.1"),
        (1.0, 3.0, "custom-call.7", "%jvp_attention.pallas_flash_.7 = (bf16[2]) custom-call(bf16[2] %x)"),
        (5.0, 6.0, "custom-call.9", "%transpose_jvp_attention.pallas_flash__.9 = bf16[2] custom-call(bf16[2] %y)"),
        (8.0, 9.0, "fusion.1", "fusion.1"),
    ]},
    host_spans=[("engine.step", 0.0, 4.5, {}), ("submit", 3.0, 4.0, {}),
                ("wait", 6.0, 10.0, {})],
    modules={"/device:TPU:0": [(0.0, 3.0, "jit_train_step(1)"),
                               (5.0, 9.0, "jit_train_step(1)"),
                               (9.5, 9.6, "jit_other")]})


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(1, 3), (0, 2), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]


def test_busy_idle_and_window():
    assert TRACE.window() == (0.0, 10.0)
    assert tr.busy_seconds(TRACE) == pytest.approx(5.0)
    assert tr.idle_share(TRACE) == pytest.approx(0.5)
    two = tr.Trace({"a": [(0, 1, "x", "x")], "b": [(0, 3, "x", "x")]},
                   [("s", 0.0, 4.0, {})])
    assert tr.busy_seconds(two) == pytest.approx(2.0)    # mean over chips


def test_a_trace_is_cut_to_its_slice_span():
    """What the profiler's own start and stop leave in a trace (an idle
    device before the slice, a stalled host after it) is not read."""
    whole = tr.Trace(TRACE.device_ops,
                     TRACE.host_spans + [("slice", 4.5, 9.2, {})],
                     TRACE.modules)
    cut = tr.cut_to_slice(whole)
    assert cut.window() == (4.5, 9.2)
    assert [o[:2] for o in cut.device_ops["/device:TPU:0"]] == \
        [(5.0, 6.0), (8.0, 9.0)]
    assert tr.busy_seconds(cut) == pytest.approx(2.0)
    assert cut.modules["/device:TPU:0"] == [(5.0, 9.0, "jit_train_step(1)")]
    # gaps 4.5-5 (no span), 6-8 and 9-9.2 (the clipped ``wait``)
    assert tr.idle_gaps(cut) == [["wait", pytest.approx(2.2)],
                                 ["no_span", pytest.approx(0.5)]]
    assert tr.cut_to_slice(TRACE) is TRACE          # no span: left whole


def test_kernel_time_by_pattern_and_top_ops():
    kc = load_module("kernel_costs", "flash_attention")
    assert tr.kernel_seconds(TRACE, kc.FORWARD) == (2.0, 1)
    assert tr.kernel_seconds(TRACE, kc.BACKWARD) == (1.0, 1)
    assert tr.kernel_seconds(TRACE, kc.PATTERNS) == (3.0, 2)
    assert tr.short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert tr.kernel_seconds(TRACE, [r"absent"]) == (0.0, 0)
    assert tr.top_ops(TRACE, 2) == [["fusion.1", 3.0], ["custom-call.7", 2.0]]


def test_idle_gaps_are_named_by_the_innermost_open_span():
    # gaps: 3-5 (middle 4.0: engine.step; submit closed at 4.0),
    # 6-8 (wait), 9-10 (wait)
    assert tr.idle_gaps(TRACE) == [["wait", 3.0], ["engine.step", 2.0]]
    assert tr.span_at(TRACE, 3.5) == "submit"
    assert tr.span_at(TRACE, 20.0) == "no_span"


def test_program_runs_and_busy_within():
    runs = tr.main_program_runs(TRACE)
    assert runs == [(0.0, 3.0), (5.0, 9.0)]
    assert [tr.busy_within(TRACE, s, e) for s, e in runs] == [3.0, 2.0]


def test_flash_cost_by_hand():
    kc = load_module("kernel_costs", "flash_attention")
    # B=2, H=3, S=8, D=4: one causal matmul = 2*3*64*4 = 1536 FLOPs
    flops, nbytes = kc.cost(1, 2, batch=2, heads=3, seq=8, head_dim=4)
    assert flops == 1536 * 2 + 1536 * 5
    assert nbytes == (4 + 8) * 2 * 3 * 8 * 4 * 2
    assert kc.cost(0, 0, batch=2, heads=3, seq=8, head_dim=4) == (0, 0)


def test_paged_decode_cost_by_hand():
    kc = load_module("kernel_costs", "paged_decode")
    # 100 cached tokens, 32 query / 8 kv heads x 128, bf16
    flops, nbytes = kc.cost(100, heads=32, kv_heads=8, head_dim=128)
    assert flops == 4 * 100 * 32 * 128
    assert nbytes == 2 * 100 * 8 * 128 * 2


def test_mfu_count_for_gpt2_345m():
    mfu = load_module("metrics", "train_mfu")
    d = {"hidden": 1024, "layers": 24, "ffn": 4096, "vocab": 50304}
    per_token = mfu.flops_per_token(d, 1024)
    assert per_token == 6 * (24 * 12 * 1024 ** 2 + 50304 * 1024) \
        + 6 * 24 * 1024 * 1024
    assert 2.2e9 < per_token < 2.3e9


def test_percentile_and_quartile_spread():
    xs = list(range(1, 102))
    assert stats.percentile(xs, 95) == pytest.approx(96.0)
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.quartile_spread([10, 10, 10, 10]) == 0
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)


def test_paged_decode_pattern_tells_decode_from_prefill():
    import re

    kc = load_module("kernel_costs", "paged_decode")
    decode = ('%program.47 = bf16[32,1,16,64]{3,2,1,0:T(8,128)(2,1)S(1)} '
              'custom-call(s32[32,64]{1,0:T(8,128)S(1)} %copy-done.3, '
              's32[32]{0:T(128)S(1)} %copy-done.48, bf16[32,1,16,64]{3,2,1,0} '
              '%copy-done.45, bf16[2049,16,16,64]{3,2,1,0} %slice.1), '
              'custom_call_target="tpu_custom_call", operand_layout')
    prefill = ('%program.12 = f32[8,4,256,128]{3,2,1,0:T(8,128)} custom-call('
               's32[256]{0} %bitcast.822, s32[1]{0} %bitcast.680, '
               'f32[8,4,256,128]{3,2,1,0} %b), '
               'custom_call_target="tpu_custom_call"')
    assert any(re.search(p, decode) for p in kc.PATTERNS)
    assert not any(re.search(p, prefill) for p in kc.PATTERNS)

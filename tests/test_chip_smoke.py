"""chip_smoke.py, on the CPU.

The smoke's phases are plain functions of a model config, so the code the
driver runs on the chip at GPT-2 345M runs here at ``gpt_tiny`` (Pallas
kernels in interpret mode) with the same checks.  What cannot run here is
the script itself: there is no CPU mode, and that refusal — shared with
``bench.py`` through ``paddle_tpu.core.chip`` — is what the second half
pins (these replace the tests of the probe-subprocess preflight that
``bench.py`` used to carry).
"""
import json
import os
import subprocess
import sys

import pytest

import bench
import chip_smoke
from paddle_tpu.core import chip
from paddle_tpu.models import gpt_tiny

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPhasesOnCpu:
    def test_train_phase(self):
        out = chip_smoke.train_phase(gpt_tiny(), seq=32, batch_per_device=1,
                                     steps=3)
        assert out["failed"] == []
        # off-TPU the oracle runs, and the result says so by name
        assert out["attention_path"] == "xla_sdpa"
        assert out["pallas_custom_calls"] == 0
        assert out["compiles"] == 1 and len(out["losses"]) == 3

    def test_serve_phase(self):
        out = chip_smoke.serve_phase(
            "gpt:tiny", max_seq=32, num_slots=4, block_size=8, min_bucket=8,
            prompt_lens=(3, 12, 26, 9, 5), shared_len=16, max_new_tokens=4)
        assert out["failed"] == []
        assert out["attention_path"] == "paged pallas (interpret)"
        assert out["buckets_used"] == out["buckets"] == [8, 16, 32]
        assert out["prefix_hit_rate"] > 0
        assert out["compile_misses"] == len(out["buckets"]) + 1

    def test_flash_phase(self):
        out = chip_smoke.flash_phase((1, 32, 2, 16))
        assert out["failed"] == [] and out["interpret"] is True

    def test_a_failed_check_is_reported(self):
        c = chip_smoke.Checks("demo")
        assert c.check("holds", True) and not c.check("breaks", False, "why")
        assert c.failed == ["demo: breaks — why"]


#: lines as XLA:TPU prints them (PR 24's decode program: the pool's layout
#: copies and per-layer slices; this PR's: the in-place scatter of a layer)
_HLO = {
    "copy": "  %copy.151 = bf16[2049,24,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(%sd_2_.1), sharding={replicated}",
    "transpose": "  ROOT %transpose.7 = bf16[16,2049,16,128]{3,2,1,0} "
                 "transpose(%param_0.3), dimensions={2,0,1,3}",
    "slice": "  %slice.12 = bf16[2049,1,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} "
             "slice(%copy.151), slice={[0:2049], [3:4], [0:16], [0:16], [0:64]}",
    "scatter": "  %fusion.5 = bf16[2049,16,16,128]{3,2,1,0:T(8,128)(2,1)} "
               "fusion(%sd_2_.1, %fusion.169, %get-tuple-element.53), "
               "kind=kCustom, calls=%fused_computation.5",
    "small": "  %copy.9 = bf16[32,16,128]{2,1,0:T(8,128)(2,1)} copy(%x.1)",
}


class TestPoolSizedMoves:
    """What the serve stage fails the decode program for on the chip."""

    LAYER_BUF = 2049 * 16 * 16 * 64 * 2         # one layer's buffer, bf16

    @pytest.mark.parametrize("op", ["copy", "transpose", "slice"])
    def test_a_move_of_a_layer_buffer_is_reported(self, op):
        (found,) = chip_smoke.pool_sized_moves(_HLO[op], self.LAYER_BUF)
        assert found.startswith(op + " ")

    @pytest.mark.parametrize("op", ["scatter", "small"])
    def test_in_place_writes_and_small_moves_are_not(self, op):
        assert chip_smoke.pool_sized_moves(_HLO[op], self.LAYER_BUF) == []

    def test_a_whole_module_is_read_line_by_line(self):
        text = "HloModule jit_decode_step\n" + "\n".join(_HLO.values())
        assert [m.split()[0] for m in chip_smoke.pool_sized_moves(
            text, self.LAYER_BUF)] == ["copy", "transpose", "slice"]


class TestNoCpuMode:
    def test_script_refuses_the_cpu_by_name(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 2
        assert "platform 'cpu'" in r.stderr
        assert r.stdout.strip() == ""        # no result of any kind

    def test_attached_chip_refuses_the_cpu(self):
        with pytest.raises(RuntimeError, match="platform 'cpu'"):
            chip.attached_chip()

    def test_unknown_device_kind_raises_from_the_peaks_table(self):
        assert chip.chip_peaks("TPU v5 lite").bf16_flops_per_s == 197e12
        with pytest.raises(ValueError, match="TPU v99"):
            chip.chip_peaks("TPU v99")

    def test_bench_measures_nothing_off_the_chip(self, monkeypatch, capsys):
        """``python bench.py`` needs no preflight child: the same
        in-process check stops it, and its failure row is tagged with the
        device it found."""
        monkeypatch.delenv("PADDLE_TPU_BENCH_SMOKE", raising=False)
        with pytest.raises(RuntimeError, match="platform 'cpu'"):
            bench.main()
        with pytest.raises(SystemExit) as e:
            bench.fail_structured("RuntimeError: a TPU is required")
        assert e.value.code == 1
        row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert row["platform"] == "cpu" and row["device_kind"] == "cpu"
        assert row["device_count"] >= 1 and row["value"] == 0.0

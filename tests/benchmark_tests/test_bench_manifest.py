"""The manifest against the contract, and every file it names."""
import json
import os
import re

import pytest

from bench_testlib import ROOT
from benchmarks.harness import manifest as mf

M = mf.load_manifest(ROOT)
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {w["name"]: w for w in M["workloads"]}


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert M["command"] == ["python3", "benchmarks/run.py"]
    assert os.path.isfile(os.path.join(ROOT, M["command"][1]))
    for p in M["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("entry", M["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert mf.NAME.match(entry["name"])
    assert entry["source"].startswith("https://")
    assert any(entry["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert mf.NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                             r"n_embd|head)$", key), f"{key} is a width"
    for kind in ("references", "adapters"):  # a family is its two files
        mf.load_module(kind, cfg["family"])
    assert any(w["config"] == entry["name"] for w in M["workloads"])


@pytest.mark.parametrize("entry", M["workloads"], ids=lambda w: w["name"])
def test_workload_entry(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for k in ("name", "config", "traffic"):
        assert mf.NAME.match(entry[k]), entry[k]
    assert entry["chips"] in (1, 4)
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    cell = mf.Cell(M, entry["name"])
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    mf.load_module("drivers", cell.mix["kind"])
    mf.load_module("generators", cell.mix["generator"])
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"


def test_cells_are_unique_and_within_the_four_chip_quota():
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs) == len(CELLS)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(pairs) // 4)


@pytest.mark.parametrize("m", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert mf.NAME.match(m["name"]) and mf.UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    for w in m.get("workloads", []):
        assert w in CELLS


def test_setup_s_is_reported_by_every_cell():
    assert "workloads" not in E2E["setup_s"]
    assert E2E["setup_s"]["bound"] == 0.1


@pytest.mark.parametrize("m", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert mf.NAME.match(m["name"]) and mf.UNIT.match(m["unit"])
    assert m["source"] in mf.SOURCES and m["better"] in ("lower", "higher")
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert callable(mf.load_module("metrics", m["name"]).read)
    moved = E2E[m["moves"]]
    for w in m.get("workloads", CELLS):
        assert w in CELLS
        assert w in moved.get("workloads", CELLS), \
            f"{w} reports {m['name']} but not {m['moves']}"
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_are_unique():
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        names = [e["name"] for e in group]
        assert len(set(names)) == len(names)


def test_every_file_under_paths_is_named_from_allowed_characters():
    for p in M["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_mix_limit_and_config_files_are_data():
    for kind in ("mixes", "limits", "configs"):
        for f in os.listdir(os.path.join(mf.BENCH_DIR, kind)):
            assert f.endswith(mf.DATA_SUFFIXES), f

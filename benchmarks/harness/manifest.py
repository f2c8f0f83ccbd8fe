"""Reads ``BENCHMARK.json`` and finds every file of a cell by its name.

Nothing here lists configurations, mixes, limits, generators, drivers,
references, kernel costs or metrics: each is one file under
``benchmarks/<kind>/`` whose basename is the name the manifest (or a mix or
configuration file) gives, so a later PR adds files and manifest entries and
edits nothing that is there.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py``, by file (names may hold
    dots and dashes, which an import statement could not spell).  A quantity
    split by what it moves (``device_idle.train``, ``device_idle.serve``) may
    share one file named without the last dotted part."""
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(BENCH_DIR, kind, name.rpartition(".")[0] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r}: {path} is missing")
    safe = re.sub(r"[^A-Za-z0-9_]", "_", name)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{safe}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, its mix, its
    limits and the metrics that list it."""

    def __init__(self, manifest: dict, workload: str):
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r}; the manifest has "
                           f"{sorted(by_name)}")
        self.entry = by_name[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        self.config_entry = cfg
        with open(os.path.join(ROOT, cfg["file"])) as f:
            self.config = json.load(f)
        self.mix = load_json("mixes", self.entry["traffic"] + ".json")
        # what ``correct`` compares is a property of the cell: the same mix
        # under another configuration reads other numbers
        self.limits = load_json("limits", workload + ".json")["limits"]
        self.run_seconds = int(manifest["run_seconds"])
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in manifest["per_layer"]
                          if workload in m.get("workloads", [workload])]

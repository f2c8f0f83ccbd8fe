"""Shared by the benchmark's CPU tests: puts the checkout on the path and
builds run contexts at ``gpt_tiny`` / ``llama_tiny`` size."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness.context import RunContext  # noqa: E402


def tiny(name: str) -> dict:
    with open(os.path.join(HERE, name + ".json")) as f:
        return json.load(f)


def context(family: str, mix: dict, tmp_path, **kw) -> RunContext:
    kw.setdefault("seed", 2 ** 31 + 11)
    kw.setdefault("seconds", 1.5)
    return RunContext(config=tiny("tiny_" + family), mix=mix,
                      limits=mix["limits"], trace=False,
                      out_dir=str(tmp_path), **kw)

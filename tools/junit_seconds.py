#!/usr/bin/env python
"""Per-file seconds of a test run, from its junit file.

    python tools/junit_seconds.py /tmp/_t1.xml > tests/file_seconds.json

prints ``{"tests/<file>.py": seconds, ...}``, the longest file first: each
case's ``time`` (set-up, call and tear-down) summed by the file it was
collected from.  ``tests/conftest.py`` hands files to xdist's workers in that
order."""
import json
import os
import sys
import xml.etree.ElementTree as ET

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def file_of(classname: str) -> str:
    """``tests.test_fleet.TestChaos`` -> ``tests/test_fleet.py``."""
    parts = classname.split(".")
    for n in range(len(parts), 0, -1):
        path = "/".join(parts[:n]) + ".py"
        if os.path.exists(os.path.join(REPO, path)):
            return path
    return classname


def file_seconds(junit_path: str) -> dict:
    seconds = {}
    for case in ET.parse(junit_path).iter("testcase"):
        path = file_of(case.get("classname", ""))
        seconds[path] = seconds.get(path, 0.0) + float(case.get("time", 0))
    return {path: round(s, 1) for path, s in
            sorted(seconds.items(), key=lambda kv: -kv[1])}


if __name__ == "__main__":
    json.dump(file_seconds(sys.argv[1]), sys.stdout, indent=0)
    print()

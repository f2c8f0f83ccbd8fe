"""``admit_host_ms`` on hand-built ring rows: an admitted request's time from
its admission's start to the pull of its first token, less the dispatch of its
prefill — on the rows a program before ISSUE 41 gives and on the rows it gives
since (a staging program before the prefill, a piece of a long prompt, the
registration after the token)."""
import pytest

import bench_testlib  # noqa: F401
from benchmarks.harness import manifest as mf
from benchmarks.harness import program_spans as ps
from benchmarks.harness.context import RunContext


def row(name, start, end, parent=None, sid=None, **attrs):
    return (name, start, end, parent, attrs, sid)


def admission(sid, t0, parts, outcome="admitted"):
    """An ``engine.admit`` span from ``t0`` whose children are ``parts``:
    ``(name, start, end)`` from the admission's start, in ms."""
    kids = [row(n, t0 + a / 1e3, t0 + b / 1e3, sid, sid + 1 + i)
            for i, (n, a, b) in enumerate(parts)]
    end = max([k[ps.END] for k in kids], default=t0) + 1e-3
    return kids + [row("engine.admit", t0, end, None, sid, outcome=outcome,
                       queue_wait_ms=0.1)]


#: the parent's rows: lookup, prefill, (registration inside the admission),
#: then the pull — 40 - 0 - 3 = 37 ms of host before the pull
OLD = admission(10, 10.0, [("engine.prefix_lookup", 1, 16),
                           ("engine.prefill", 20, 23),
                           ("engine.first_token", 40, 42)])
#: this PR's rows: 9 - 0 - 3 = 6 ms, and the registration after the token
NEW = admission(20, 11.0, [("engine.prefix_lookup", 1, 4),
                           ("engine.stage", 4, 5),
                           ("engine.prefill", 5, 8),
                           ("engine.first_token", 9, 24),
                           ("engine.register", 24, 30)])
#: a prompt in two pieces: 30 - 4 - 6 = 20 ms
PIECES = admission(30, 12.0, [("engine.prefix_lookup", 1, 3),
                              ("engine.stage", 3, 4),
                              ("engine.prefill", 4, 8),
                              ("engine.stage", 18, 19),
                              ("engine.prefill", 19, 25),
                              ("engine.first_token", 30, 31),
                              ("engine.register", 31, 33)])
FAILED = admission(40, 13.0, [("engine.prefix_lookup", 1, 900)],
                   outcome="failed")
DEFERRED = admission(50, 13.5, [("engine.prefix_lookup", 1, 700)],
                     outcome="deferred")


def read(rows, monkeypatch, quiet=(9.0, 20.0)):
    monkeypatch.setattr(ps, "rows", lambda: list(rows))
    ctx = RunContext(config={}, mix={}, limits={}, seed=1, seconds=1.0,
                     trace=True)
    result = {"trace": None, "spans": [], "counters": {},
              "facts": {"kind": "serve", "window": list(quiet),
                        "quiet_window": list(quiet)}}
    return mf.load_module("metrics", "admit_host_ms").read(result, ctx)


@pytest.mark.parametrize("rows, want", [
    (OLD, 37.0), (NEW, 6.0), (PIECES, 20.0),
    (OLD + NEW + PIECES, 20.0),                     # the median of the three
    (NEW + FAILED + DEFERRED, 6.0),                 # admitted requests only
], ids=["parent", "staged", "pieces", "median", "admitted_only"])
def test_admit_host_ms_is_the_admission_less_its_dispatch(rows, want,
                                                          monkeypatch):
    assert read(rows, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("rows, quiet", [
    ([], (9.0, 20.0)),                              # a program with no ring
    (FAILED, (9.0, 20.0)),                          # nothing admitted
    (NEW, (11.5, 20.0)),                            # outside the quiet window
    ([r for r in NEW if r[ps.NAME] != "engine.first_token"], (9.0, 20.0)),
], ids=["no_ring", "none_admitted", "outside", "no_pull"])
def test_admit_host_ms_reads_nothing_where_there_is_nothing(rows, quiet,
                                                            monkeypatch):
    assert read(rows, monkeypatch, quiet) is None


def test_admit_host_ms_is_declared_for_the_serving_cells():
    m = {p["name"]: p for p in mf.load_manifest()["per_layer"]}["admit_host_ms"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == \
        ("ms", "lower", "program_span", "engine", "ttft_p50_ms")
    serving = [w["name"] for w in mf.load_manifest()["workloads"]
               if ".serve-" in w["name"]]
    assert m["workloads"] == serving and len(serving) == 6

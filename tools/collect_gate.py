#!/usr/bin/env python
"""Collection gate: fail CI when any test module errors at import.

Why this exists: between r05 and PR 2, a single version-fragile import
(``from jax import shard_map``) errored **45 of 45** test modules at
collection — and the suite "ran" anyway, reporting the handful of tests
that still collected.  A green-ish run that silently lost 98% of its
tests is worse than a red one.  This gate runs ``pytest --collect-only``
and exits nonzero on ANY collection error, so an import break can never
again zero out the suite unnoticed.

A second failure class (ISSUE 7): the serving stack's zero-recompile and
no-host-round-trip invariants are now *statically* checkable.
``--lint`` runs ``python -m tools.tpulint paddle_tpu/`` (the
recompile-hazard/host-sync AST lint — every suppression must carry a
reason) and ``python -m tools.tpulint.shape_closure`` (regenerates the
serving executable-cache key manifest and diffs it against the
committed ``tools/shape_manifest.json``, so an unexpected new compile
key fails the gate instead of surfacing as a steady-state cache miss).

Usage::

    python tools/collect_gate.py [pytest-target ...]   # default: tests/
    python tools/collect_gate.py --lint

Exit codes: 0 = everything collects; 1 = collection errors (listed on
stderr), an active lint finding, or shape-manifest drift; pytest's own
exit code for other failures (usage error etc.).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    run_lint = "--lint" in args
    if run_lint:
        args.remove("--lint")
    targets = args or ["tests/"]
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "--continue-on-collection-errors", "-p", "no:cacheprovider",
         *targets],
        cwd=REPO, env=env, capture_output=True, text=True)
    out = r.stdout + r.stderr
    errors = re.findall(r"^ERROR (\S+)", out, flags=re.M)
    m = re.search(r"(\d+) tests? collected", out)
    collected = int(m.group(1)) if m else 0
    if errors:
        print(f"collect_gate: FAIL — {len(errors)} module(s) error at "
              f"collection ({collected} tests still collect):",
              file=sys.stderr)
        for mod in errors:
            print(f"  ERROR {mod}", file=sys.stderr)
        # surface the first traceback block for diagnosis
        tb = re.search(r"_{10,} ERROR collecting .*?(?=_{10,}|=+ )", out,
                       flags=re.S)
        if tb:
            print(tb.group(0)[:4000], file=sys.stderr)
        return 1
    if collected == 0:
        print("collect_gate: FAIL — zero tests collected "
              "(wrong target or pytest broke before collection):",
              file=sys.stderr)
        print(out[-2000:], file=sys.stderr)
        return 1
    rc = paging_gate(env, collected_output=out)
    if rc:
        return rc
    if run_lint:
        rc = lint_gate(env)
        if rc:
            return rc
    print(f"collect_gate: OK — {collected} tests collect, 0 errors")
    return 0


def lint_gate(env=None) -> int:
    """Static-analysis gate (ISSUE 7): tpulint over ``paddle_tpu/``
    must be clean (suppressions all carry reasons), and the serving
    shape manifest must match a fresh enumeration of the executable-
    cache key space (``tools/tpulint/shape_closure.py``)."""
    if env is None:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
    for what, cmd in (
            ("tpulint", [sys.executable, "-m", "tools.tpulint",
                         "paddle_tpu/"]),
            ("shape manifest", [sys.executable, "-m",
                                "tools.tpulint.shape_closure"])):
        r = subprocess.run(cmd, cwd=REPO, env=env,
                           capture_output=True, text=True)
        if r.returncode:
            print(f"collect_gate: FAIL — {what} gate "
                  f"(`{' '.join(cmd[1:])}`):", file=sys.stderr)
            sys.stderr.write(r.stdout[-3000:] + r.stderr[-3000:])
            return 1
        tail = (r.stdout.strip().splitlines() or [""])[-1]
        print(f"collect_gate: {tail}")
    return 0


#: Test files whose coverage must ALWAYS ride in tier-1: collect at
#: least one test, and carry no ``slow`` marks (tier-1 deselects slow,
#: so a slow mark here would silently drop the coverage).
TIER1_CRITICAL = {
    "tests/test_paging.py": "the KV block allocator",
    "tests/test_fleet.py": "fleet supervision/failover",
    "tests/test_overload.py": "priority/preemption/shed scheduling",
    "tests/test_tracing.py": "request-lifecycle tracing/flight recorder",
    "tests/test_paged_kernel.py":
        "Pallas paged-attention kernel parity vs the jnp reference",
    "tests/test_device_sampling.py":
        "on-device sampling parity vs the host oracle",
    "tests/test_sentry.py":
        "divergence-sentry detection/rollback and bitwise parity",
    "tests/test_train_obs.py":
        "training step observatory (timeline/compile/cost ledgers)",
    "tests/test_durability.py":
        "request journal, crash recovery & rolling weight hot-swap",
    "tests/test_spec_decode.py":
        "speculative decoding: draft/verify/accept parity & rollback",
    "tests/test_tp_overlap.py":
        "TP compute/collective overlap: chunked-schedule parity & "
        "exposed-collective pins",
    "tests/test_elastic_reshard.py":
        "elastic reconfiguration: resharded-resume bitwise proofs, "
        "exactly-once data schedule, mesh watchdog & SIGKILL drill",
    "tests/test_sharded_serving.py":
        "tensor-parallel serving: sharded-vs-single-chip bitwise "
        "parity, mesh-shape recovery contract & shard-group hot swap",
    "tests/test_degraded_serving.py":
        "degraded-mode serving: cross-mesh journal replay bitwise "
        "both directions, viability ladder & shard-group failover",
    "tests/test_tenancy.py":
        "multi-tenant serving: adapter-lane bitwise-off proof, "
        "per-tenant prefix isolation, grammar-masked decoding & "
        "tenant crash-recovery",
}


def paging_gate(env=None, collected_output=None) -> int:
    """Tier-1 must always exercise the critical serving suites
    (``TIER1_CRITICAL``): each file collects at least one test and NONE
    of its tests is marked ``slow``.

    ``collected_output`` is main()'s own ``--collect-only -q`` listing —
    reused for the collects-at-all half so the gate adds only ONE extra
    pytest subprocess per file (the ``-m slow`` filter, the only new
    signal)."""
    if env is None:
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")

    def _collect(extra, target):
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "--collect-only", "-q",
             "-p", "no:cacheprovider", *extra, target],
            cwd=REPO, env=env, capture_output=True, text=True)
        # "20 tests collected", "5/20 tests collected (15 deselected)",
        # or "no tests collected (20 deselected)"
        m = re.search(r"(\d+)(?:/\d+)? tests? collected",
                      r.stdout + r.stderr)
        return int(m.group(1)) if m else 0

    counts = {}
    for target, what in TIER1_CRITICAL.items():
        if collected_output is not None:
            total = len(re.findall(rf"^{re.escape(target)}::",
                                   collected_output, flags=re.M))
        else:
            total = _collect([], target)
        if total == 0:
            print(f"collect_gate: FAIL — {target} collects no tests "
                  f"({what} would go untested)", file=sys.stderr)
            return 1
        slow = _collect(["-m", "slow"], target)
        if slow:
            print(f"collect_gate: FAIL — {slow} test(s) in {target} are "
                  f"marked slow; tier-1 deselects them, so {what} would "
                  f"go untested", file=sys.stderr)
            return 1
        counts[target] = total
    print("collect_gate: tier-1-critical OK — " + ", ".join(
        f"{n} tests in {t}" for t, n in counts.items()) +
        "; none marked slow")
    return 0


if __name__ == "__main__":
    sys.exit(main())

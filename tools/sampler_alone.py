"""The serving sampler alone, on the chip: device time of one jitted
``device_sample`` call per vocabulary and lane pattern, read from a profiler
trace (the ``XLA Modules`` line: one event per executed program), so that the
host's dispatch is not in the number; and the tokens it returned, to hold
against another tree's.

    python tools/sampler_alone.py [--repo PATH] [--out FILE.json]

``--repo`` names another checkout whose ``paddle_tpu.serving.sampling`` is
timed instead (the parent commit, unpacked by ``git archive``): one process a
tree, the chip belongs to one at a time.  Refuses to time anything but a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS = 32
VOCABS = (50304, 129280)         # GPT-2 345M's, JoyAI-LLM-Flash's
CALLS = 40
#: lanes ``(temperature, top_k, top_p)`` of the rows that sample; the rest
#: are greedy
PATTERNS = {
    "greedy": (0, (0.0, 0, 1.0)),
    "8_rows_k50_p09": (8, (0.8, 50, 0.9)),
    "1_row_k0_p09": (1, (0.8, 0, 0.9)),
}


def time_on_device(programs: dict) -> tuple:
    """``({name: median device ms}, {name: output})`` of ``programs``
    (``{name: (fn, args)}``), each jitted, warmed, and then called ``CALLS``
    times in a profiler session of its own (equal programs share one
    executable and one name in a trace, so the session tells them apart)."""
    import jax

    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "harness"))
    import trace_reduce

    ms, outs = {}, {}
    for name, (fn, args) in programs.items():
        fn = jax.jit(fn)
        outs[name] = jax.block_until_ready(fn(*args))
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                for _ in range(CALLS):
                    out = fn(*args)
                jax.block_until_ready(out)
            trace = trace_reduce.read_xplane(trace_reduce.find_xplane(d))
        ms[name] = statistics.median(
            (t1 - t0) * 1e3 for rows in trace.modules.values()
            for t0, t1, _name in rows)
    return ms, outs


def lanes(jnp, sampled: int, params):
    temp, top_k, top_p = params
    rows = jnp.arange(SLOTS) < sampled
    return (jnp.where(rows, temp, 0.0).astype(jnp.float32),
            jnp.where(rows, top_k, 0).astype(jnp.int32),
            jnp.where(rows, top_p, 1.0).astype(jnp.float32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=ROOT)
    ap.add_argument("--out")
    ns = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(ns.repo))
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.sampling import device_sample

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "error": f"not a TPU: {dev.platform}"}))
        return 1
    programs = {}
    for vocab in VOCABS:
        logits = 3.0 * jax.random.normal(jax.random.PRNGKey(vocab),
                                         (SLOTS, vocab), jnp.float32)
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(SLOTS)).astype(
            jnp.uint32)
        for pattern, (sampled, params) in PATTERNS.items():
            programs[f"sampler_{vocab}_{pattern}"] = (
                device_sample, (logits, *lanes(jnp, sampled, params), keys))
    ms, outs = time_on_device(programs)
    # same inputs, same tokens: compared between two trees' rows
    row = {"ok": True, "repo": os.path.abspath(ns.repo),
           "device_kind": dev.device_kind, "slots": SLOTS, "device_ms": ms,
           "tokens": {name: [int(t) for t in toks]
                      for name, (toks, _keys) in outs.items()}}
    print(json.dumps(row, indent=1))
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as f:
            json.dump(row, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

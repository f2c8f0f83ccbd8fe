"""Shape-closure analyzer: prove the serving engine's executable-cache
key set is CLOSED, at trace time, from config alone.

The zero-steady-state-recompile contract (docs/SERVING.md "Bucketed
prefill & the zero-recompile guarantee") has so far been checked *after
the fact*: run traffic, count executable-cache misses.  This module
turns it into a static proof with three steps:

1. **Enumerate** the compiled-program key space from config: one
   prefill program per bucket (powers of two from ``min_bucket`` to
   ``max_seq``) plus ONE decode program, for each engine variant
   (plain, speculative, tenancy).  Each
   entry is built with ``StaticFunction.get_concrete_program`` — state
   discovery runs under ``jax.eval_shape`` and ``jax.jit`` is lazy, so
   enumeration performs **zero XLA compiles**.
2. **Probe closure**: sweep representative runtime argument instances —
   every prompt length ``1..max_seq``, every slot index, every
   active-mask population — map each through the engine's own cache-key
   function (``spec_of`` + ``_extra_key``), and assert every key lands
   in the enumerated set.  Because cache keys depend only on
   shape/dtype/stop_gradient (never values), the sweep covers the whole
   runtime argument space the engine can construct.
3. **Emit** ``tools/shape_manifest.json``: per-entry argument specs,
   lifted-state/write counts, ``jax.eval_shape`` output shapes, and a
   sha256 per cache key + one digest over the whole key set.  CI
   (``collect_gate.py --lint``) regenerates and diffs the manifest — an
   unexpected new compile key fails the gate as a manifest drift
   instead of showing up three PRs later as a steady-state cache miss.

Fleet replicas multiply executables, not keys: every replica constructs
its own ``Engine`` (own ``StaticFunction``, own program cache) over the
same config, so the per-replica key set is this same closed set and the
manifest records the multiplication (``fleet`` section) rather than
re-enumerating it.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from typing import Dict, List, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_MANIFEST = os.path.join(REPO, "tools", "shape_manifest.json")

#: The canonical serving config the manifest proves closure for — kept
#: in lockstep with ``bench.py --serving`` (same model, slots, buckets)
#: so the proof covers exactly the programs the bench and the serving
#: tests exercise.
CANONICAL = {
    "model": "gpt:tiny",
    "num_slots": 4,
    "max_seq": 64,
    "min_bucket": 8,
    "block_size": 8,
    "fleet_replicas": 2,    # bench fleet smoke: 2 replicas
    # speculative section (ISSUE 15): the opt-in draft/verify key set —
    # a paged engine with speculation on replaces the decode key with
    # draft_prefill[b=*] + draft_decode + verify (the proposal column
    # index and the per-slot emission caps are argument VALUES)
    "spec_draft": "gpt:tiny",
    "spec_k": 4,
    # sharded section (ISSUE 18): per-mesh-shape key sets for
    # Engine(mesh=serving_mesh(mp)).  Cache keys exclude sharding
    # (shape/dtype/stop_gradient only), so each section must be the
    # SAME closed set — build_manifest enumerates under each mesh and
    # raises if a single key differs from the unsharded enumeration.
    # model=1 joined the enumeration with degraded-mode serving
    # (ISSUE 19): it is no longer only the degenerate tautology a
    # size-1 axis filters out of every placement spec — it is the
    # floor of the viability ladder a failed shard group REBUILDS at
    # (tests/test_degraded_serving.py, the bench kill-a-shard drill),
    # so the manifest must prove the degraded shape's key space is the
    # same closed set tier-1 warms.
    "serving_mesh_shapes": [2, 1],
    # tenancy section (ISSUE 20): a paged engine with adapter lanes AND
    # grammar lanes on.  Adapter ids / LoRA banks / grammar DFA tables
    # enter the programs as LIFTED STATE (values, never shapes), so the
    # section must enumerate the EXACT key set of the plain paged
    # config — build_manifest asserts flatness and records the
    # n_state_inputs drift per entry (the lanes are the drift).
    "adapters": {"max_adapters": 2, "rank": 4},
    "grammar": {"eos_token_id": 1, "max_elems": 3, "max_digits": 2},
}


def _sha(obj) -> str:
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


def _leaf_specs(key) -> List[List]:
    """Human-readable tensor-leaf specs out of a spec_of key tree:
    ``[["1x8", "paddle.int64", true], ...]`` in argument order."""
    out: List[List] = []

    def walk(node):
        if not isinstance(node, tuple) or not node:
            return
        tag = node[0]
        if tag == "T":
            shape, dtype, sg = node[1], node[2], node[3]
            out.append(["x".join(map(str, shape)) or "scalar",
                        str(dtype), bool(sg)])
        elif tag == "dict":
            for _k, v in node[1]:
                walk(v)
        elif tag in ("list", "tuple"):
            for child in node[1]:
                walk(child)

    walk(key)
    return out


def _cache_key(fn, args) -> tuple:
    """The EXACT executable-cache key ``StaticFunction.__call__`` would
    use for this argument instance — computed without building (so a
    probe can never grow the cache it is probing)."""
    from paddle_tpu.jit.trace import _flatten_io, spec_of

    leaves: List = []
    args_tree = _flatten_io(list(args), leaves)
    kwargs_tree = _flatten_io({}, leaves)
    return (spec_of(args_tree, leaves), spec_of(kwargs_tree, leaves),
            fn._extra_key(args))


def _out_shapes(prog) -> List[List]:
    """``jax.eval_shape`` of the built (never compiled) program: the
    declared output avals, proving the program signature is fully
    abstract-derivable."""
    import jax

    state_arrays = [k.current() for k in prog.state_keys]
    sd, sk = prog._split_state(state_arrays)
    outs, _writes = jax.eval_shape(prog.jitted, prog._probe_args, sd, sk)
    return [["x".join(map(str, o.shape)) or "scalar", str(o.dtype)]
            for o in outs]


def _build_engine(variant: str, cfg: dict, mesh=None):
    """``variant``: ``"paged"`` (the plain engine), ``"speculative"`` or
    ``"tenancy"`` (the same engine with that feature on)."""
    from paddle_tpu.serving import Engine, JsonArrayGrammar, SpecConfig

    kwargs = dict(num_slots=cfg["num_slots"], max_seq=cfg["max_seq"],
                  min_bucket=cfg["min_bucket"],
                  block_size=cfg["block_size"], mesh=mesh)
    if variant == "speculative":
        kwargs.update(speculation=SpecConfig(
            draft_model=cfg["spec_draft"], k=cfg["spec_k"]))
    if variant == "tenancy":
        kwargs.update(adapters=dict(cfg["adapters"]),
                      grammars={"json": JsonArrayGrammar(**cfg["grammar"])})
    eng = Engine(Engine.resolve_model(cfg["model"]), **kwargs)
    eng._build_steps()
    return eng


def _prefill_args(eng, bucket: int, *, L: int = 1, slot: int = 0,
                  start: int = 0):
    """Argument tensors exactly as ``Engine._admit`` constructs them
    (shapes/dtypes are what key the cache; values are free)."""
    import numpy as np
    from paddle_tpu.core.tensor import to_tensor

    ids = np.zeros((1, bucket), dtype=np.int64)
    return [to_tensor(ids), to_tensor(np.int32(slot)),
            to_tensor(np.int32(L)), to_tensor(np.int32(start))]


def _decode_args(eng, *, n_active: int = 0):
    """Decode takes ONLY the active mask since on-device sampling: the
    input token ids live in the engine's device-side token lane
    (``Engine.sampler.tokens``), lifted state rather than an argument."""
    import numpy as np
    from paddle_tpu.core.tensor import to_tensor

    active = np.zeros((eng.num_slots,), dtype=np.int32)
    active[:n_active] = 1
    return [to_tensor(active)]


def _draft_prefill_args(eng, bucket: int, *, L: int = 1, slot: int = 0):
    """Draft prefill is always full-prompt into the draft's dense cache
    (no prefix cache, no ``start``)."""
    import numpy as np
    from paddle_tpu.core.tensor import to_tensor

    ids = np.zeros((1, bucket), dtype=np.int64)
    return [to_tensor(ids), to_tensor(np.int32(slot)),
            to_tensor(np.int32(L))]


def _draft_decode_args(eng, *, n_active: int = 0, j: int = 0):
    """Draft decode adds only the proposal COLUMN index ``j`` (a traced
    scalar — k sequential calls per round share one compiled key)."""
    import numpy as np
    from paddle_tpu.core.tensor import to_tensor

    return _decode_args(eng, n_active=n_active) + [to_tensor(np.int32(j))]


def _verify_args(eng, *, n_active: int = 0, cap: int = 1):
    """Verify adds only the per-slot emission caps (values, not
    shapes): ``[slots] int32`` like the active mask."""
    import numpy as np
    from paddle_tpu.core.tensor import to_tensor

    caps = np.full((eng.num_slots,), cap, dtype=np.int32)
    return _decode_args(eng, n_active=n_active) + [to_tensor(caps)]


def enumerate_config(variant: str, cfg: dict,
                     mesh=None) -> Tuple[dict, dict]:
    """Build every program the config admits; returns
    ``(manifest_section, key_index)`` where ``key_index`` maps each raw
    cache key to its entry name (for the closure probe).  With ``mesh``,
    the engine is sharded and tracing runs under its mesh context — the
    exact programs a sharded engine builds (still zero XLA compiles)."""
    from contextlib import nullcontext

    from paddle_tpu.core.autograd import no_grad

    eng = _build_engine(variant, cfg, mesh=mesh)
    entries: Dict[str, dict] = {}
    key_index: Dict[tuple, str] = {}
    mesh_ctx = eng.shard.context() if eng.shard is not None \
        else nullcontext()
    with mesh_ctx, no_grad():
        plan = [(f"prefill[b={b}]", eng._prefill_fn, _prefill_args(eng, b))
                for b in eng.buckets]
        if eng.spec is None:
            plan.append(("decode", eng._decode_fn, _decode_args(eng)))
        else:
            # speculation replaces the plain decode program: draft
            # prefill per bucket (dense draft cache — no start
            # argument), ONE draft decode, ONE verify
            plan.extend(
                (f"draft_prefill[b={b}]", eng._draft_prefill_fn,
                 _draft_prefill_args(eng, b)) for b in eng.buckets)
            plan.append(("draft_decode", eng._draft_decode_fn,
                         _draft_decode_args(eng)))
            plan.append(("verify", eng._verify_fn, _verify_args(eng)))
        for name, fn, args in plan:
            key = _cache_key(fn, args)
            prog = fn.get_concrete_program(*args)
            prog._probe_args = [t._value() for t in args]
            entries[name] = {
                "args": _leaf_specs(key[0]),
                "n_state_inputs": len(prog.state_keys),
                "n_writes": len(prog.write_keys),
                "out": _out_shapes(prog),
                "key_sha256": _sha(key),
            }
            key_index[key] = name
    fns = [eng._prefill_fn]
    fns += [eng._decode_fn] if eng.spec is None else \
        [eng._draft_prefill_fn, eng._draft_decode_fn, eng._verify_fn]
    n_prog = sum(len(fn.program_cache) for fn in fns)
    if n_prog != len(entries):
        raise AssertionError(
            f"{variant}: enumerated {len(entries)} entries but the "
            f"program cache holds {n_prog} — the key space is not what "
            "the enumeration thinks it is")
    section = {
        "engine": {"variant": variant, "num_slots": cfg["num_slots"],
                   "max_seq": cfg["max_seq"],
                   "min_bucket": cfg["min_bucket"],
                   "block_size": cfg["block_size"],
                   **({"spec_draft": cfg["spec_draft"],
                       "spec_k": cfg["spec_k"]}
                      if variant == "speculative" else {}),
                   **({"adapters": dict(cfg["adapters"]),
                       "grammar": dict(cfg["grammar"])}
                      if variant == "tenancy" else {})},
        "buckets": list(eng.buckets),
        "programs": len(entries),
        "entries": entries,
    }
    return section, (eng, key_index)


def probe_closure(eng, key_index: Dict[tuple, str]) -> List[str]:
    """Sweep runtime argument instances and return the (hopefully empty)
    list of instances whose cache key escapes the enumerated set.

    Coverage: every prompt length 1..max_seq at both slot extremes with
    every block-aligned prefix-hit start inside the bucket, plus every decode active-mask population 0..num_slots.  Keys depend
    only on shape/dtype/stop_gradient, so this sweep is exhaustive over
    everything the engine can construct at runtime."""
    from paddle_tpu.core.autograd import no_grad

    escapes: List[str] = []
    with no_grad():
        for L in range(1, eng.max_seq + 1):
            for slot in (0, eng.num_slots - 1):
                # prefix hits shrink the tail bucket: starts are
                # block-aligned, tail = L - start >= 1
                for start in range(0, L, eng.block_size):
                    bucket = eng.bucket_for(L - start)
                    args = _prefill_args(eng, bucket, L=L, slot=slot,
                                         start=start)
                    key = _cache_key(eng._prefill_fn, args)
                    if key not in key_index:
                        escapes.append(
                            f"prefill L={L} slot={slot} start={start} "
                            f"-> unenumerated key {_sha(key)}")
        if eng.spec is None:
            for n_active in range(eng.num_slots + 1):
                key = _cache_key(eng._decode_fn, _decode_args(
                    eng, n_active=n_active))
                if key not in key_index:
                    escapes.append(f"decode n_active={n_active} -> "
                                   f"unenumerated key {_sha(key)}")
        else:
            for L in range(1, eng.max_seq + 1):
                for slot in (0, eng.num_slots - 1):
                    bucket = eng.bucket_for(L)
                    key = _cache_key(
                        eng._draft_prefill_fn,
                        _draft_prefill_args(eng, bucket, L=L, slot=slot))
                    if key not in key_index:
                        escapes.append(
                            f"draft_prefill L={L} slot={slot} -> "
                            f"unenumerated key {_sha(key)}")
            for n_active in range(eng.num_slots + 1):
                for j in range(eng.spec.k):
                    key = _cache_key(eng._draft_decode_fn,
                                     _draft_decode_args(
                                         eng, n_active=n_active, j=j))
                    if key not in key_index:
                        escapes.append(
                            f"draft_decode n_active={n_active} j={j} "
                            f"-> unenumerated key {_sha(key)}")
                for cap in (1, eng.spec.k + 1):
                    key = _cache_key(eng._verify_fn, _verify_args(
                        eng, n_active=n_active, cap=cap))
                    if key not in key_index:
                        escapes.append(
                            f"verify n_active={n_active} cap={cap} -> "
                            f"unenumerated key {_sha(key)}")
    return escapes


def build_manifest(cfg: dict = CANONICAL) -> dict:
    """Enumerate + probe every engine variant; raises on any closure
    escape (an open key space must never be written as a 'proof')."""
    configs = {}
    for variant in ("paged", "speculative", "tenancy"):
        section, (eng, key_index) = enumerate_config(variant, cfg)
        escapes = probe_closure(eng, key_index)
        if escapes:
            raise AssertionError(
                f"shape closure VIOLATED for {variant} (the compiled-key "
                f"set is open):\n  " + "\n  ".join(escapes[:10]))
        section["closure_probe"] = {
            "prefill_instances": 2 * sum(
                len(range(0, L, eng.block_size))
                for L in range(1, eng.max_seq + 1)),
            "decode_instances": (
                eng.num_slots + 1 if eng.spec is None
                # draft_prefill sweep + draft_decode (j) + verify (cap)
                else 2 * eng.max_seq
                + (eng.num_slots + 1) * (eng.spec.k + 2)),
            "escapes": 0,
        }
        configs[variant] = section
    # tenancy flatness (ISSUE 20): adapter + grammar lanes must add
    # ZERO cache keys — the tenancy section's key set is byte-identical
    # to plain paged (lanes are lifted state: values, never shapes).
    # What DOES grow is each program's lifted-state input count (the id
    # lane, per-target LoRA A/B banks, grammar tables + per-slot
    # grammar id/state lanes); the drift is recorded per entry so a
    # silent future change (a lane becoming an argument, a bank
    # splitting per slot) diffs loudly instead of passing as noise.
    paged_keys = {n: e["key_sha256"]
                  for n, e in configs["paged"]["entries"].items()}
    ten_keys = {n: e["key_sha256"]
                for n, e in configs["tenancy"]["entries"].items()}
    if ten_keys != paged_keys:
        raise AssertionError(
            "tenancy: compiled-key set differs from plain paged — "
            "adapter/grammar lanes must never widen the key space "
            f"(paged {sorted(paged_keys)} vs tenancy {sorted(ten_keys)})")
    configs["tenancy"]["keys_equal_paged"] = True
    configs["tenancy"]["state_input_drift"] = {
        name: e["n_state_inputs"]
        - configs["paged"]["entries"][name]["n_state_inputs"]
        for name, e in configs["tenancy"]["entries"].items()}
    # sharded sections (ISSUE 18): re-enumerate the plain engine under
    # each canonical serving mesh shape.  The cache key excludes
    # sharding, so every section must be the SAME closed key set — any
    # difference means a sharded engine would compile keys the
    # manifest never proved closed, and is raised here, not recorded.
    sharded = {}
    for mp in cfg.get("serving_mesh_shapes", []):
        from paddle_tpu.serving import mesh_shape_key, serving_mesh

        mesh = serving_mesh(mp)
        mkey = mesh_shape_key(mesh)
        section, _ = enumerate_config("paged", cfg, mesh=mesh)
        if {n: e["key_sha256"] for n, e in section["entries"].items()} \
                != paged_keys:
            raise AssertionError(
                f"sharded paged @ {mkey}: compiled-key set "
                "differs from the unsharded enumeration — sharding "
                "must never widen the key space")
        sharded[mkey] = {"paged": {"programs": section["programs"],
                                   "keys_equal_unsharded": True}}
    # fleet replicas serve the plain engine (speculation is a per-
    # engine opt-in, not a fleet default)
    per_replica = {"paged": configs["paged"]["programs"]}
    manifest = {
        "_comment": [
            "Shape-closure proof for the serving engine's executable",
            "cache (docs/ANALYSIS.md): every compiled-program cache key",
            "the canonical config can produce, enumerated via",
            "jax.eval_shape (zero XLA compiles) and closure-probed over",
            "all runtime argument instances.  CI regenerates and diffs",
            "this file (`collect_gate.py --lint`); regenerate",
            "deliberately with `python -m tools.tpulint.shape_closure",
            "--write` when the key space changes ON PURPOSE.",
        ],
        "version": 1,
        "model": cfg["model"],
        "configs": configs,
        "sharded": {
            "note": "Engine(mesh=serving_mesh(mp)) key sets per mesh "
                    "shape: cache keys exclude sharding, so each "
                    "section is the SAME closed set the configs above "
                    "prove — one warmed executable set per mesh shape, "
                    "zero steady-state recompiles sharded",
            "mesh_shapes": sharded,
        },
        "fleet": {
            "replicas": cfg["fleet_replicas"],
            "programs_per_replica": per_replica,
            "total_executables": cfg["fleet_replicas"]
            * sum(per_replica.values()),
            "note": "each replica owns its own Engine and program "
                    "cache over the same config: replicas multiply "
                    "executables, never cache keys",
        },
    }
    manifest["digest"] = _sha(sorted(
        (variant, name, e["key_sha256"])
        for variant, sec in configs.items()
        for name, e in sec["entries"].items()))
    return manifest


def diff_manifests(committed: dict, fresh: dict) -> List[str]:
    """Entry-level drift between the committed manifest and a fresh
    enumeration; empty when identical where it matters."""
    problems: List[str] = []
    for variant in sorted(set(committed.get("configs", {}))
                         | set(fresh["configs"])):
        old = committed.get("configs", {}).get(variant, {}).get("entries", {})
        new = fresh["configs"].get(variant, {}).get("entries", {})
        for name in sorted(set(old) | set(new)):
            if name not in old:
                problems.append(f"{variant}/{name}: NEW compile key "
                                f"(sha {new[name]['key_sha256']}) — not "
                                "in the committed manifest")
            elif name not in new:
                problems.append(f"{variant}/{name}: compile key vanished "
                                "(committed but no longer enumerated)")
            elif old[name] != new[name]:
                changed = [k for k in new[name] if old[name].get(k)
                           != new[name][k]]
                problems.append(f"{variant}/{name}: entry changed "
                                f"({', '.join(changed)})")
        # the section's non-entry fields (engine config, buckets,
        # closure-probe counts) are part of the proof too — a
        # hand-edited block_size or probe count must not pass
        old_sec = {k: v for k, v in committed.get("configs", {})
                   .get(variant, {}).items() if k != "entries"}
        new_sec = {k: v for k, v in fresh["configs"]
                   .get(variant, {}).items() if k != "entries"}
        if old_sec != new_sec:
            changed = [k for k in sorted(set(old_sec) | set(new_sec))
                       if old_sec.get(k) != new_sec.get(k)]
            problems.append(f"{variant}: config section drifted "
                            f"({', '.join(changed)})")
    for field in ("version", "model", "sharded", "fleet"):
        if committed.get(field) != fresh.get(field):
            problems.append(
                f"{field}: committed {committed.get(field)!r} != fresh "
                f"{fresh.get(field)!r}")
    if committed.get("digest") != fresh["digest"] and not problems:
        problems.append("digest mismatch with identical entries "
                        "(manifest hand-edited?)")
    return problems


_USAGE = ("usage: python -m tools.tpulint.shape_closure "
          "[--write | --check] [--path FILE]")


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    write = False
    path = DEFAULT_MANIFEST
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--write":
            write = True
        elif a == "--check":
            pass                        # the default mode, spelled out
        elif a == "--path":
            if i + 1 >= len(args):
                print(f"shape_closure: --path needs a file argument\n"
                      f"{_USAGE}", file=sys.stderr)
                return 2
            i += 1
            path = args[i]
        else:
            # a typo'd --write running check mode and printing OK would
            # convince an operator the manifest was regenerated
            print(f"shape_closure: unknown argument {a!r}\n{_USAGE}",
                  file=sys.stderr)
            return 2
        i += 1
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the sharded sections need a multi-device host platform; the flag
    # only takes effect BEFORE the (lazy) jax import inside
    # build_manifest, which is why main() sets it, not the library
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    fresh = build_manifest()
    n_keys = sum(s["programs"] for s in fresh["configs"].values())
    if write:
        with open(path, "w") as f:
            json.dump(fresh, f, indent=1, sort_keys=False)
            f.write("\n")
        print(f"shape_closure: wrote {os.path.relpath(path, REPO)} — "
              f"{n_keys} compile keys, closure probes clean")
        return 0
    try:
        with open(path) as f:
            committed = json.load(f)
    except (OSError, ValueError) as e:
        print(f"shape_closure: FAIL — cannot read committed manifest "
              f"{path}: {e}\n  (generate it: python -m "
              "tools.tpulint.shape_closure --write)", file=sys.stderr)
        return 1
    problems = diff_manifests(committed, fresh)
    if problems:
        print(f"shape_closure: FAIL — executable-cache key space "
              f"drifted from {os.path.relpath(path, REPO)}:",
              file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        print("  if intentional, regenerate: python -m "
              "tools.tpulint.shape_closure --write", file=sys.stderr)
        return 1
    print(f"shape_closure: OK — {n_keys} compile keys match the "
          f"committed manifest; closure probes clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""HLO cost & fingerprint accounting — what XLA says a program costs.

The one-shot ``tools/perf_fingerprint.py`` proved the idea: compile
(without running) the exact program the bench times and record the
structural facts a perf regression would move.  This module generalizes
it into a reusable per-executable :class:`CostLedger` the training
observatory, the bench, and ``tools/step_ablation.py``'s offline mode
all share:

- **XLA cost analysis** per compiled program: flops, bytes accessed,
  transcendentals, and the optimized-HLO op mix (dot / fusion /
  all_gather / reduce_scatter / collective_permute / while / ...);
- **analytic roofline**: arithmetic intensity (flops/byte) and the
  hardware-independent *analytic MFU* — the best MFU the program's
  flop/byte mix admits on a given chip spec,
  ``(F/P) / max(F/P, B/W)`` — so a memory-bound step is visible as
  such on CPU, before any hardware run;
- **schedule fingerprint**: a digest over the optimized module's
  opcode sequence *in program order*.  Two identical compiles produce
  identical text, so the fingerprint is stable run-to-run — and it is
  exactly the CPU-verifiable surface ROADMAP item 3 needs: when the
  T3-style compute/collective overlap lands, the overlapped schedule
  (collectives interleaved between the dots they hide behind) moves
  the fingerprint, and a regression that serializes them again moves
  it back — assertable without a TPU.

Everything here rides the executable cache: analysis calls
``StaticFunction.get_concrete_program`` (the SAME key the real call
uses — zero new cache entries, pinned by key-set equality in
tests/test_train_obs.py) and ``CompiledProgram.compiled_stats()``
(which shares jax's lower/compile cache with normal calls).

CPU lowering caveat (same as the fingerprint tool): XLA:CPU sees the
same jaxpr — same flops, dot shapes, collective structure — but not
Pallas custom kernels (they fall back to the XLA path off-TPU).
"""
from __future__ import annotations

import hashlib
import re
from typing import Dict, List, Optional

from ..core.chip import chip_peaks

__all__ = ["CostLedger", "count_hlo_ops", "opcode_sequence",
           "schedule_fingerprint", "analyze_static_fn",
           "collective_exposure", "HLO_OPS", "COLLECTIVE_OPS"]

# one HLO instruction per line: `%name = <type> opcode(...)` — shared
# with tools/perf_fingerprint.py (which imports these, so the tracked
# artifact and the ledger can never count differently)
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.-]+ = .+? ([\w-]+)\(")

#: opcodes counted into ``hlo_counts``.  Collectives are split out
#: because the overlap work is judged on exactly those — including the
#: async start/done halves TPU schedules emit, so a started-but-
#: unfinished collective is never invisible to the ledger.
HLO_OPS = ("dot", "fusion", "custom-call", "all-reduce", "all-gather",
           "reduce-scatter", "collective-permute", "all-to-all", "while",
           "convolution",
           "all-reduce-start", "all-reduce-done",
           "all-gather-start", "all-gather-done",
           "collective-permute-start", "collective-permute-done")

#: every collective opcode ``collective_exposure`` classifies; the
#: ``*-start`` halves anchor async pairs (their ``*-done`` is the
#: consumer-side marker, not an independent collective)
COLLECTIVE_OPS = frozenset((
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "all-reduce-start", "all-gather-start",
    "collective-permute-start"))

# full instruction parse for collective_exposure: name, result type(s),
# opcode, args — a superset of what _INSTR captures
_DEF = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.-]+) = (.+?) ([\w-]+)\((.*)$")
_SHAPE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_OPERAND = re.compile(r"%?([\w.-]+)")
_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "c128": 16,
                "f32": 4, "s32": 4, "u32": 4,
                "f16": 2, "bf16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}


def _result_bytes(type_text: str) -> int:
    """Largest element of the (possibly tuple) result type in bytes —
    the payload size of a collective (async starts alias their operand
    into the result tuple; max picks the payload, not the sum)."""
    best = 0
    for dt, dims in _SHAPE.findall(type_text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES.get(dt, 4))
    return best


def opcode_sequence(hlo_text: str) -> List[str]:
    """Every instruction opcode of the optimized module, in text
    (= program) order — the raw material of the schedule fingerprint."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out.append(m.group(1))
    return out


def count_hlo_ops(hlo_text: str, ops=HLO_OPS) -> Dict[str, int]:
    """Occurrences of each tracked opcode (keys underscored:
    ``all-gather`` → ``all_gather``)."""
    counts = {op.replace("-", "_"): 0 for op in ops}
    opset = set(ops)
    for op in opcode_sequence(hlo_text):
        if op in opset:
            counts[op.replace("-", "_")] += 1
    return counts


def schedule_fingerprint(hlo_text: str) -> str:
    """sha256 over the opcode sequence in program order (names and ids
    stripped — only the *shape of the schedule* is hashed).  Identical
    program + identical XLA ⇒ identical fingerprint; reordering one
    collective against one dot moves it."""
    seq = "\n".join(opcode_sequence(hlo_text))
    return hashlib.sha256(seq.encode()).hexdigest()[:16]


def collective_exposure(hlo_text: str) -> dict:
    """Classify every collective in an optimized HLO module as
    **overlapped** or **exposed**.

    A collective is overlapped iff compute (a ``dot``, ``fusion`` or
    ``convolution``) is scheduled strictly between it and the point its
    result is first needed: for an async ``*-start`` that window closes
    at the matching ``*-done``; for a sync collective it closes at the
    first instruction consuming its result.  A collective whose result
    is never consumed in its computation is counted exposed (its
    latency has nothing to hide behind).  The walk is per-computation
    (fusion/while bodies are separate scopes) and purely textual, so
    the verdict is as deterministic as the schedule fingerprint.

    Returns ``{"total", "overlapped", "exposed", "exposed_bytes",
    "collectives": [{"opcode", "overlapped", "bytes"}, ...]}``.
    """
    comps: List[List[tuple]] = [[]]
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{"):
            comps.append([])            # new computation scope
            continue
        m = _DEF.match(line)
        if not m:
            continue
        name = m.group(1).lstrip("%")
        ops = frozenset(_OPERAND.findall(m.group(4)))
        comps[-1].append((name, m.group(3), ops, _result_bytes(m.group(2))))

    compute_ops = ("dot", "fusion", "convolution")
    out: List[dict] = []
    for instrs in comps:
        for i, (name, opcode, _ops, nbytes) in enumerate(instrs):
            if opcode not in COLLECTIVE_OPS:
                continue
            if opcode.endswith("-start"):
                done = opcode[:-len("-start")] + "-done"
                end = next((j for j in range(i + 1, len(instrs))
                            if instrs[j][1] == done
                            and name in instrs[j][2]), None)
            else:
                end = next((j for j in range(i + 1, len(instrs))
                            if name in instrs[j][2]), None)
            overlapped = end is not None and any(
                instrs[j][1] in compute_ops for j in range(i + 1, end))
            out.append({"opcode": opcode, "overlapped": overlapped,
                        "bytes": nbytes})

    exposed = [d for d in out if not d["overlapped"]]
    return {
        "total": len(out),
        "overlapped": len(out) - len(exposed),
        "exposed": len(exposed),
        "exposed_bytes": int(sum(d["bytes"] for d in exposed)),
        "collectives": out,
    }


def _roofline(flops: float, bytes_accessed: float, chip: str) -> dict:
    """Analytic roofline of a program on ``chip`` — a ``device_kind`` of
    ``core.chip.CHIP_PEAKS``, always named by the caller: a program
    compiled on the CPU says which chip it is being projected onto."""
    peaks = chip_peaks(chip)
    peak, bw = peaks.bf16_flops_per_s, peaks.hbm_bytes_per_s
    t_compute = flops / peak
    t_memory = bytes_accessed / bw if bytes_accessed else 0.0
    t_step = max(t_compute, t_memory) or 1e-30
    return {
        "chip": chip,
        "arithmetic_intensity": round(flops / max(bytes_accessed, 1.0), 3),
        "ridge_intensity": round(peak / bw, 3),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "roofline_step_ms": round(t_step * 1e3, 6),
        "analytic_mfu": round(t_compute / t_step, 6),
    }


def analyze_static_fn(static_fn, *args, chip: str) -> dict:
    """Cost-analyze one compiled program of a ``to_static`` function at
    the given example arguments.

    Uses the function's OWN cache key (``get_concrete_program`` — an
    already-warm program is reused, a cold one is built by eval_shape
    discovery) and ``compiled_stats()`` (one lower+compile, shared with
    jax's executable cache; nothing is executed).  Returns the record
    :class:`CostLedger` stores — flops / bytes / transcendentals / op
    counts / memory analysis / fingerprint / roofline.
    """
    from ..jit.trace import _flatten_io

    prog = static_fn.get_concrete_program(*args)
    leaves = []
    _flatten_io(list(args), leaves)
    # compiled_stats reads the last arg arrays; a program that has never
    # executed has none — feed the example args (same specs as the key)
    prog._last_arg_arrays = [t._value() for t in leaves]
    stats = prog.compiled_stats()
    hlo = stats.pop("hlo")
    cost = stats.pop("cost", {})
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes_accessed", 0.0))
    exposure = collective_exposure(hlo)
    exposure.pop("collectives")         # summary only; keep records light
    rec = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "hlo_counts": count_hlo_ops(hlo),
        "hlo_instructions": len(opcode_sequence(hlo)),
        "memory": dict(stats),          # argument/output/temp/peak bytes
        "fingerprint": schedule_fingerprint(hlo),
        "collective_exposure": exposure,
        **_roofline(flops, bytes_accessed, chip),
    }
    return rec


class CostLedger:
    """Per-executable cost/fingerprint ledger.

    ``add(name, static_fn, *args)`` analyzes one program and stores the
    record under ``name``; ``tokens_per_step``/``n_params`` (optional)
    add the 6ND cross-check — ``flops_vs_6nd`` is XLA's flop count over
    the scaling-literature analytic ``6 · n_params · tokens``, ~1.0 at
    real scale (the 345M bench measures 1.04; tiny configs run higher
    because attention and the vocab CE dominate 6N there).

    The ledger-level :meth:`fingerprint` digests every program's
    schedule fingerprint, so ONE value asserts the whole step's
    compiled structure.
    """

    def __init__(self, chip: str):
        chip_peaks(chip)                 # an unknown device_kind raises
        self.chip = chip
        self.programs: Dict[str, dict] = {}

    def add(self, name: str, static_fn, *args,
            tokens_per_step: Optional[int] = None,
            n_params: Optional[int] = None) -> dict:
        rec = analyze_static_fn(static_fn, *args, chip=self.chip)
        if tokens_per_step and n_params:
            model_flops = 6.0 * float(n_params) * float(tokens_per_step)
            rec["model_flops_6nd"] = model_flops
            rec["flops_vs_6nd"] = round(rec["flops"] / model_flops, 4)
        self.programs[name] = rec
        return rec

    def fingerprint(self) -> str:
        """Digest over every program's schedule fingerprint (sorted by
        name) — the one-value regression surface."""
        h = hashlib.sha256()
        for name in sorted(self.programs):
            h.update(f"{name}={self.programs[name]['fingerprint']}\n"
                     .encode())
        return h.hexdigest()[:16]

    def analytic_mfu(self, name: Optional[str] = None) -> float:
        """The named program's analytic MFU (default: ``train_step`` if
        present, else the single program, else 0.0)."""
        if name is None:
            name = "train_step" if "train_step" in self.programs else \
                (next(iter(self.programs)) if self.programs else None)
        if name is None:
            return 0.0
        return float(self.programs[name]["analytic_mfu"])

    def stats(self) -> dict:
        """JSON-ready snapshot (``profiler.train_stats()`` surface):
        numeric cost facts per program plus the combined fingerprint."""
        progs = {}
        for name, r in self.programs.items():
            progs[name] = {k: r[k] for k in
                           ("flops", "bytes_accessed", "transcendentals",
                            "arithmetic_intensity", "analytic_mfu",
                            "roofline_step_ms", "hlo_instructions")}
            progs[name]["hlo_counts"] = dict(r["hlo_counts"])
            if "flops_vs_6nd" in r:
                progs[name]["flops_vs_6nd"] = r["flops_vs_6nd"]
        return {"chip": self.chip, "programs": progs,
                "fingerprint": self.fingerprint(),
                "analytic_mfu": self.analytic_mfu()}

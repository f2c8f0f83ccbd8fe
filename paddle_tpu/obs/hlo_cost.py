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
           "collective_exposure", "HLO_OPS", "COLLECTIVE_OPS",
           "scope_of", "scope_map", "scope_maps", "instructions", "UNSCOPED",
           "STRUCTURAL_PARTS"]

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


# -- who an instruction belongs to -------------------------------------------
# Every instruction of an optimized module carries, in its metadata, the
# op_name JAX built it under: ``jit(<program>)/`` + the name-scope path
# (``jax.named_scope``; a ``Layer`` call while ``to_static`` traces) with the
# transforms wrapped around it + the primitive.  XLA:TPU keeps it on fusions
# (their root's), on ``while`` / ``conditional`` and on the ops of their
# bodies; a profiler trace's ``XLA Ops`` event carries the instruction's
# *name* only, so name -> scope is the join that gives device time an owner
# (docs/OBSERVABILITY.md "Device time by scope").

#: the scope of an instruction no rule below gives one
UNSCOPED = ""

#: parts of an op_name that JAX adds for the program's structure, not for a
#: scope somebody named: control flow and calls (what the five benchmark
#: cells' programs contain, and their kin)
STRUCTURAL_PARTS = frozenset((
    "while", "body", "cond", "body_fun", "cond_fun", "closed_call",
    "core_call", "pjit", "checkpoint", "remat", "rematted_computation",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "custom_lin", "scan", "switch", "named_call"))
_BRANCH = re.compile(r"branch_\d+_fun")
_SCOPE_PART = re.compile(r"[A-Za-z0-9_.]+")
_WRAPPED = re.compile(r"([A-Za-z_]\w*)\((.*)\)", re.S)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEES = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.-]+)|branch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+) .*\{\s*$")
_MODULE = re.compile(r"^HloModule ([\w.-]+)")
_PERCENT_NAME = re.compile(r"%([\w.-]+)")


def _top_level(path: str) -> List[str]:
    """``path`` split on the ``/`` that lie outside every parenthesis."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _unwrap(parts: List[str], out: List[str]) -> bool:
    """Appends to ``out`` the plain parts of ``parts`` with every transform
    (``jvp(...)``, ``transpose(...)``, ``vmap(...)``, nested) taken off and
    every ``jit(<op>)`` / ``pjit(<op>)`` dropped; true if a ``transpose(``
    wrapped any of them."""
    bwd = False
    for part in parts:
        m = _WRAPPED.fullmatch(part)
        if m is None:
            out.append(part)
        elif m.group(1) not in ("jit", "pjit"):
            bwd |= m.group(1) == "transpose"
            bwd |= _unwrap(_top_level(m.group(2)), out)
    return bwd


def scope_of(op_name: str):
    """``(scope, direction)`` of one instruction's op_name — **the rule**:

    1. drop the leading ``jit(<program>)/`` (an op_name without it is no
       path JAX built: ``sk[4]`` of a parameter, ``reduce_sum`` of a
       reducer — :data:`UNSCOPED`), and where the inliner joined several
       whole paths everything before the last ``jit(<program>)/`` (a path
       joined to itself without it, ``a/b/a/b``, reads once);
    2. drop the trailing primitive (the last part outside every parenthesis,
       where it is a plain word: ``dot_general``, ``while``, ``mul``);
    3. unwrap the transforms; ``direction`` is ``"bwd"`` if a ``transpose(``
       wrapped any part (JAX's linear transpose, and the tape's backward
       sweep, which runs a node under ``transpose(<its forward's path>)``),
       else ``"fwd"``;
    4. drop the parts JAX adds for structure (:data:`STRUCTURAL_PARTS`,
       ``branch_N_fun``, ``jit(<op>)``) and whatever is no name of
       ``[A-Za-z0-9_.]`` (an einsum's ``bqhd,bkhd->bhqk``);
    5. what is left, joined by ``/``, is the scope
       (``gpt/layers/3/attn/qkv_proj``, ``loss.streamed_ce``,
       ``optimizer.adamw``); nothing left is :data:`UNSCOPED`."""
    parts = _top_level(op_name)
    if not parts[0].startswith("jit("):
        return UNSCOPED, "fwd"       # a parameter's name, a reducer's op
    # XLA's inliner joins a call site's op_name to its callee's, which is
    # a whole path again (``jit(f)/a/jit(searchsorted)/jit(f)/a/.../lt``,
    # once a trip of an unrolled search): the last whole path counts
    parts = parts[len(parts) - parts[::-1].index(parts[0]):]
    if parts and "(" not in parts[-1]:
        parts = parts[:-1]
    plain: List[str] = []
    bwd = _unwrap(parts, plain)
    kept = [p for p in plain
            if _SCOPE_PART.fullmatch(p) and p not in STRUCTURAL_PARTS
            and not _BRANCH.fullmatch(p)]
    while kept and len(kept) % 2 == 0 \
            and kept[:len(kept) // 2] == kept[len(kept) // 2:]:
        kept = kept[:len(kept) // 2]      # a path joined to itself: once
    return "/".join(kept), "bwd" if bwd else "fwd"


def instructions(hlo_text: str) -> List[tuple]:
    """``(computation, name, opcode, op_name or None, called computations,
    operands)`` of every instruction of an HLO module's text, in text
    order; ``operands`` are the ``%names`` its text mentions."""
    out, comp = [], ""
    for line in hlo_text.splitlines():
        m = _DEF.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        rest = m.group(4)
        named = _OP_NAME.search(rest)
        callees = []
        for one, many in _CALLEES.findall(rest):
            callees += [one] if one else \
                [c.strip().lstrip("%") for c in many.split(",") if c.strip()]
        out.append((comp, m.group(1).lstrip("%"), m.group(3),
                    named.group(1) if named else None, callees,
                    _PERCENT_NAME.findall(rest.split(", metadata=")[0])))
    return out


def scope_map(hlo_text: str) -> dict:
    """``{"module": <HloModule name>, "instructions": {name: (scope,
    direction)}}`` over the instructions of **every** computation of an
    optimized module (a loop's body and a conditional's branches execute as
    device events of their own).  An instruction whose op_name JAX built
    under the program reads :func:`scope_of`.  One without (a fusion whose
    root lost it, a ``copy`` named after the parameter it copies, the
    ``copy-start`` / ``copy-done`` of a weight moved ahead of its use)
    takes the most frequent scoped reading among the instructions of the
    computations it calls (``calls=``, ``body=``, ``branch_computations=``
    ...), else the reading of the first instruction of its computation that
    consumes it and has one, else it is :data:`UNSCOPED`."""
    m = _MODULE.match(hlo_text)
    rows = instructions(hlo_text)
    got: Dict[str, tuple] = {}
    members: Dict[str, List[str]] = {}
    for comp, name, _opcode, op_name, _callees, _operands in rows:
        members.setdefault(comp, []).append(name)
        got[name] = scope_of(op_name) if op_name else (UNSCOPED, "fwd")
    # innermost computations come first in the text, so one pass in text
    # order resolves a chain (a conditional without a name whose branch is
    # a fusion without a name)
    for _comp, name, _opcode, op_name, callees, _operands in rows:
        if (op_name or "").startswith("jit(") or not callees:
            continue
        votes: Dict[tuple, int] = {}
        for c in callees:
            for inner in members.get(c, ()):
                if got[inner][0] != UNSCOPED:
                    votes[got[inner]] = votes.get(got[inner], 0) + 1
        if votes:
            got[name] = max(votes, key=votes.get)
    # a consumer comes after what it consumes, so one pass from the end
    # resolves a chain (copy-start <- copy-done <- the fusion it feeds)
    consumer: Dict[tuple, str] = {}
    for comp, name, _opcode, op_name, _callees, operands in reversed(rows):
        if got[name][0] == UNSCOPED and (comp, name) in consumer \
                and not (op_name or "").startswith("jit("):
            got[name] = got[consumer[comp, name]]
        if got[name][0] != UNSCOPED:
            for operand in operands:
                consumer[comp, operand] = name  # the earliest one stays
    return {"module": m.group(1) if m else "", "instructions": got}


def scope_maps(modules) -> List[dict]:
    """The :func:`scope_map` of every program this process built through
    ``jit.to_static`` whose HLO module is named in ``modules`` (the names a
    profile's ``XLA Modules`` line holds, without their ``(<id>)``): each
    built on first request, a ``jit.scope_map`` span, and kept.  Programs
    of one name (``jit_prefill_step``: one a bucket) give one map each; the
    caller joins them."""
    from ..jit.trace import program_texts

    return [t.scope_map() for t in program_texts(frozenset(modules))]


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

"""Device time by the scope that built it: every ``XLA Ops`` event of a
traced slice put down to the ``jax.named_scope`` path (and the ``Layer``
calls) its HLO instruction was traced under, by program.

The trace names an event by its instruction (``%fusion.4806 = ...``:
``trace_reduce.short_name`` cuts the name out, the third field of a
``device_ops`` row) and every event lies inside an ``XLA Modules`` event that
names its program (``jit_train_step(<id>)``).  The program keeps, for each
program it compiled, the map from an instruction's name to ``(scope,
direction)`` (``paddle_tpu.obs.scope_maps``: built on request from the
program's own optimized HLO, one ``jit.scope_map`` span each).  Name -> scope
is a join; this module makes it, per event and per program — not on
``trace_summary.json``, which merges an instruction name across programs.

What a v5e trace's ``XLA Ops`` line looks like (looked at by hand, PR 36, the
train cell's slice): events of one execution nest — a ``while`` event spans
the events of its body's instructions, one set a trip, a ``conditional`` the
events of the branch it took; a fusion's inner instructions are no events;
nothing else overlaps.  So **each moment of device-busy time goes to the
innermost event open then** (the one that started last), and the scopes' sum
over a program's execution *equals* its device-busy time: a ``while`` keeps
only what its body's events leave uncovered (the train step's two CE loops:
22.27 and 28.03 ms, all of it their bodies' fusions).  The device's record
ends a little before the slice does, so the plane's last ``XLA Modules``
event can be an execution cut short (3.2 ms of a 399 ms step, with its first
125 events): :func:`whole_runs` leaves it out.

A program that cannot state its maps (an older commit) gives every reader
here ``None``; a module no map is found for, an instruction its map lacks,
one that two same-named programs put under different scopes (``ambiguous``)
and one the map reads unscoped all count as **unscoped**.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import program_spans

NO_MAP, NOT_IN_MAP, AMBIGUOUS, UNSCOPED = (
    "(no map for the module)", "(instruction not in the map)",
    "(ambiguous between same-named programs)", "(unscoped)")
LEFT_OVER = (NO_MAP, NOT_IN_MAP, AMBIGUOUS, UNSCOPED)
_KEY = "device_scopes"       # where a result keeps what was computed once
SMALL = 0.01                 # a program under this share prints no table
_INDEX = re.compile(r"\d+")


def module_name(event_name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def program_maps(trace) -> Optional[List[dict]]:
    """The program's scope maps for the modules the trace's ``XLA Modules``
    line names; ``None`` where the program has no such function."""
    try:
        from paddle_tpu.obs import scope_maps
    except ImportError:
        return None
    return scope_maps({module_name(n) for mods in trace.modules.values()
                       for _s, _e, n in mods})


def innermost_seconds(events) -> Dict[str, float]:
    """Seconds by key over ``events`` = ``(start, end, key)``: every moment
    in which some event is open goes to the open event that started last.
    The values' sum is the length of the events' union."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []          # (end, key), innermost last
    cursor = 0.0

    def close_until(t):
        nonlocal cursor
        while stack and stack[-1][0] <= t:
            end, key = stack.pop()
            if end > cursor:
                out[key] = out.get(key, 0.0) + end - cursor
                cursor = end

    for s, e, key in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack and s > cursor:
            out[stack[-1][1]] = out.get(stack[-1][1], 0.0) + s - cursor
        cursor = max(cursor, s) if stack else s
        stack.append((e, key))
    close_until(float("inf"))
    return out


def joined(maps: List[dict], names) -> Dict[str, tuple]:
    """One ``instruction -> (scope, direction)`` for an execution whose
    events are called ``names``, out of the same-named programs' ``maps``:
    those that hold every one of the names if any does (a bucket's program is
    told by its instructions), else all; a name two of them read differently
    is ``(AMBIGUOUS, "fwd")``."""
    fits = [m for m in maps if names <= m["instructions"].keys()] or maps
    if len(fits) == 1:
        return fits[0]["instructions"]
    out: Dict[str, tuple] = {}
    for m in fits:
        for name, where in m["instructions"].items():
            if out.setdefault(name, where) != where:
                out[name] = (AMBIGUOUS, "fwd")
    return out


def whole_runs(mods, ops_end: float) -> List[tuple]:
    """``mods`` (a plane's sorted ``XLA Modules`` events) without an
    execution the end of the device's record cut short: the last one, where
    no device event follows it and it is under half as long as the median of
    its program's others."""
    if len(mods) < 2 or mods[-1][1] < ops_end:
        return mods
    s, e, name = mods[-1]
    others = sorted(m[1] - m[0] for m in mods[:-1] if m[2] == name)
    if others and e - s < 0.5 * others[len(others) // 2]:
        return mods[:-1]
    return mods


def by_program(trace, maps: List[dict]) -> Dict[str, dict]:
    """``{program: {"runs": n, "busy_s": s, "seconds": {(scope, direction):
    s}}}`` over the **whole executions** in the slice, on the first device
    plane; ``scope`` is a map's reading or one of :data:`LEFT_OVER`."""
    if not trace.device_ops or not trace.modules:
        return {}
    plane = sorted(trace.device_ops)[0]
    ops = trace.device_ops[plane]
    mods = whole_runs(sorted(trace.modules.get(plane, [])),
                      max(o[1] for o in ops))
    maps_of: Dict[str, List[dict]] = {}
    for m in maps:
        maps_of.setdefault(m["module"], []).append(m)
    out: Dict[str, dict] = {}
    i = 0
    for s, e, event_name in mods:
        while i < len(ops) and ops[i][0] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][0] < e:
            j += 1
        inside = [o for o in ops[i:j] if o[1] <= e]
        program = module_name(event_name)
        took = innermost_seconds((o[0], o[1], o[2]) for o in inside)
        got = out.setdefault(program, {"runs": 0, "busy_s": 0.0,
                                       "seconds": {}})
        got["runs"] += 1
        got["busy_s"] += sum(took.values())
        candidates = maps_of.get(program)
        where = joined(candidates, took.keys()) if candidates else None
        for name, seconds in took.items():
            if where is None:
                key = (NO_MAP, "fwd")
            else:
                key = where.get(name, (NOT_IN_MAP, "fwd"))
                if key[0] == "":
                    key = (UNSCOPED, key[1])
            got["seconds"][key] = got["seconds"].get(key, 0.0) + seconds
    return out


def fold(scope: str) -> str:
    """A scope by layer kind: ``gpt/layers/3/attn`` -> ``gpt/layers/*/attn``."""
    return "/".join("*" if _INDEX.fullmatch(p) else p
                    for p in scope.split("/"))


def table(program: str, got: dict, rows: int = 20) -> List[str]:
    """The by-scope table of one program, folded by layer kind: ms per
    execution, forward | backward, largest first."""
    folded: Dict[str, List[float]] = {}
    for (scope, direction), seconds in got["seconds"].items():
        cell = folded.setdefault(fold(scope), [0.0, 0.0])
        cell[direction == "bwd"] += seconds
    per = 1e3 / got["runs"]
    ranked = sorted(folded.items(), key=lambda kv: -sum(kv[1]))
    left = sum(sum(v) for k, v in ranked if k in LEFT_OVER)
    lines = [f"device time by scope: {program}, {got['runs']} whole "
             f"executions, {per * got['busy_s']:.3f} ms busy each, "
             f"{100.0 * left / got['busy_s'] if got['busy_s'] else 0:.2f} % "
             f"under no scope; ms per execution, forward | backward"]
    for scope, (fwd, bwd) in ranked[:rows]:
        lines.append(f"  {per * fwd:10.4f} | {per * bwd:10.4f}  {scope}")
    rest = ranked[rows:]
    if rest:
        lines.append(f"  {per * sum(v[0] for _k, v in rest):10.4f} | "
                     f"{per * sum(v[1] for _k, v in rest):10.4f}  "
                     f"({len(rest)} more scopes)")
    return lines


def read(result, say=None) -> Optional[Dict[str, dict]]:
    """:func:`by_program` of ``result["trace"]`` with the program's own
    maps, computed once a result and printed as tables the first time;
    ``None`` without a trace or without maps."""
    if _KEY in result:
        return result[_KEY]
    trace = result.get("trace")
    maps = None if trace is None else program_maps(trace)
    got = None if maps is None else by_program(trace, maps)
    if got and say is not None:
        built = [r for r in program_spans.rows()
                 if r[program_spans.NAME] == "jit.scope_map"]
        say(f"device time by scope: {len(maps)} scope maps of the program; "
            f"building them took {sum(map(program_spans.seconds, built)):.2f}"
            f"s in {len(built)} jit.scope_map spans")
        busy = sum(g["busy_s"] for g in got.values())
        small = []
        for program in sorted(got, key=lambda p: -got[p]["busy_s"]):
            if got[program]["busy_s"] < SMALL * busy:
                small.append(program)
                continue
            for line in table(program, got[program]):
                say(line)
        if small:
            say("device time by scope: under 1 % of the busy time each, "
                "seconds (executions): " + ", ".join(
                    f"{p} {got[p]['busy_s']:.5f} ({got[p]['runs']})"
                    for p in small))
    result[_KEY] = got or None
    return result[_KEY]


def main_program(got: Dict[str, dict]) -> str:
    """The program that took most device time (a train cell's step)."""
    return max(got, key=lambda p: got[p]["busy_s"])


def scope_ms(got: dict, scope: str) -> Optional[float]:
    """Device ms a whole execution of one program under ``scope``: every
    reading whose path holds ``scope`` as one of its parts, forward and
    backward; ``None`` where no event lies under it."""
    hit = [s for (path, _d), s in got["seconds"].items()
           if scope in path.split("/")]
    return 1e3 * sum(hit) / got["runs"] if hit else None


def program_scope_ms(result, program: Optional[str], scope: str, say=None
                     ) -> Optional[float]:
    """:func:`scope_ms` of ``program`` (``None``: the main program) in a
    result's traced slice."""
    got = read(result, say)
    if not got:
        return None
    program = program or main_program(got)
    return scope_ms(got[program], scope) if program in got else None


def unscoped_share(result, say=None) -> Optional[float]:
    """Percent of the device-busy time of the slice's whole executions, all
    programs, that no map puts under a scope."""
    got = read(result, say)
    if not got:
        return None
    busy = sum(g["busy_s"] for g in got.values())
    by: Dict[str, float] = {}
    for g in got.values():
        for (scope, _d), s in g["seconds"].items():
            if scope in LEFT_OVER:
                by[scope] = by.get(scope, 0.0) + s
    if say is not None:
        say("device_unscoped: " + ", ".join(
            f"{k} {v:.4f}s" for k, v in sorted(by.items())) +
            f" of {busy:.4f}s busy in whole executions")
    return 100.0 * sum(by.values()) / busy if busy > 0 else None

"""What the program says about itself, for the per-layer readers: the rows of
its span ring (``paddle_tpu.obs.spans``: ``engine.*`` phases of
``Engine.step``, ``jit.trace`` / ``jit.compile`` of every program-cache
miss), the device-side names it gives its programs (``jit_decode_step``,
``jit_prefill_step``) and scopes (``loss.streamed_ce``, ``optimizer.adamw``),
and the offset between the ring's clock and the profiler trace's.

The ring is stamped on ``time.perf_counter()``, the clock of the benchmark's
own ``result["spans"]`` and of ``facts["window"]``; ``trace_reduce`` keeps only
``bench.*`` host events of a trace, so the program's rows are taken from the
ring and, for the one reader that lays them over device events, moved onto
the trace's clock by ``clock_offset``.

A program without the ring (an older commit), without a span or without a
name gives a reader nothing to read: every function here then returns an
empty list or ``None``, never raises, and the metric is left out of the line.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Tuple

from benchmarks.harness import trace_reduce

#: a ring row: (name, start, end, parent sid, attrs, sid)
NAME, START, END, PARENT, ATTRS, SID = range(6)
MATCH_LIMIT_S = 0.2e-3    # a worse alignment of the two clocks is not used


def rows() -> List[tuple]:
    """The program's span rows, oldest first; ``[]`` where the program has
    no ring."""
    try:
        from paddle_tpu.obs import spans
    except ImportError:
        return []
    return spans.snapshot()


def named(all_rows, name: str, t0: Optional[float] = None,
          t1: Optional[float] = None) -> List[tuple]:
    """Rows called ``name`` that lie wholly inside ``[t0, t1]``."""
    return [r for r in all_rows if r[NAME] == name
            and (t0 is None or r[START] >= t0)
            and (t1 is None or r[END] <= t1)]


def children(all_rows) -> Dict[int, List[tuple]]:
    """Rows by the ``sid`` of their parent."""
    out: Dict[int, List[tuple]] = {}
    for r in all_rows:
        if r[PARENT] is not None:
            out.setdefault(r[PARENT], []).append(r)
    return out


def seconds(row) -> float:
    return row[END] - row[START]


def window_start(result) -> Optional[float]:
    """``perf_counter`` at the window's start: the driver's own fact, or
    the first of the benchmark's spans (a train window opens with one)."""
    win = result["facts"].get("window")
    if win:
        return float(win[0])
    starts = [s for _n, s, _e, _a in result.get("spans") or []]
    return min(starts) if starts else None


def quiet_window(result) -> Optional[Tuple[float, float]]:
    """The window as far as the profiler's start, which stalls the loop."""
    q = result["facts"].get("quiet_window")
    return (float(q[0]), float(q[1])) if q else None


# -- the two clocks ----------------------------------------------------------

def clock_offset(result, say=None) -> Optional[float]:
    """Seconds to add to a ``perf_counter`` reading to get the trace's time.

    The benchmark's ``engine.step`` spans are in both clocks: in
    ``result["spans"]`` (``perf_counter``) and, as ``bench.engine.step``, in
    the trace's host plane.  The trace's interior spans (those the slice's
    edges did not clip) are found in the benchmark's list as the run of
    consecutive spans whose durations agree best; the offset is the median
    difference of their starts, and the residual (the largest deviation of a
    start from it) is printed.  A residual over ``MATCH_LIMIT_S`` means the
    clocks were not matched: ``None``."""
    trace = result.get("trace")
    if trace is None:
        return None
    t0, t1 = trace.window()
    in_trace = [(s, e) for n, s, e, _a in trace.host_spans
                if n == "engine.step" and s > t0 and e < t1]
    in_bench = [(s, e) for n, s, e, _a in result.get("spans") or []
                if n == "engine.step"]
    m = len(in_trace)
    if m < 3 or len(in_bench) < m:
        return None
    want = [e - s for s, e in in_trace]
    best = None
    for k in range(len(in_bench) - m + 1):
        err = max(abs((e - s) - w)
                  for (s, e), w in zip(in_bench[k:k + m], want))
        if best is None or err < best[0]:
            best = (err, k)
    err, k = best
    diffs = [ts - bs for (ts, _te), (bs, _be)
             in zip(in_trace, in_bench[k:k + m])]
    offset = statistics.median(diffs)
    residual = max(abs(d - offset) for d in diffs)
    if say is not None:
        say(f"clock_offset: {m} bench.engine.step spans of the trace matched "
            f"at {k} of {len(in_bench)}; durations agree to "
            f"{1e6 * err:.1f} us, starts to {1e6 * residual:.1f} us")
    if max(err, residual) > MATCH_LIMIT_S:
        return None
    return offset


# -- device-side names -------------------------------------------------------

def program_runs(trace, name: str) -> List[Tuple[float, float]]:
    """``(start, end)`` of every whole execution in the slice, on the first
    device plane, of the program called ``name`` (an ``XLA Modules`` event
    reads ``<name>(<fingerprint>)``)."""
    if trace is None or not trace.modules:
        return []
    mods = trace.modules[sorted(trace.modules)[0]]
    return [(s, e) for s, e, n in mods
            if n == name or n.startswith(name + "(")]


def program_device_ms(result, name: str, say=None) -> Optional[float]:
    """Median device-busy milliseconds of one whole execution of ``name``."""
    trace = result.get("trace")
    runs = program_runs(trace, name)
    if not runs:
        return None
    ran = busy_intervals(trace)
    busy = [overlap([run], ran) for run in runs]
    if say is not None:
        say(f"{name}: {len(runs)} whole executions in the slice, device-busy "
            f"ms median {1e3 * statistics.median(busy):.3f} min "
            f"{1e3 * min(busy):.3f} max {1e3 * max(busy):.3f}")
    return 1e3 * statistics.median(busy)


def scope_share(result, scope: str, say=None) -> Optional[float]:
    """Percent of the step program's device-busy time, over its whole
    executions in the slice, spent in events that carry ``scope`` (a
    ``jax.named_scope`` of the program) anywhere in their text: the
    instruction's name, or a string stat such as its op_name."""
    trace = result.get("trace")
    if trace is None or not trace.device_ops:
        return None
    runs = trace_reduce.main_program_runs(trace)
    if not runs:
        return None
    reg = re.compile(re.escape(scope))
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    hit = [(o[0], o[1]) for o in ops if reg.search(o[3])]
    if not hit:
        return None
    scoped = overlap(hit, runs)          # nested events count once
    busy = overlap(runs, busy_intervals(trace))
    if say is not None:
        say(f"{scope}: {len(hit)} device events carry the scope, "
            f"{scoped:.4f}s of {busy:.4f}s device-busy in {len(runs)} whole "
            f"executions of the step's program")
    return 100.0 * scoped / busy if busy > 0 else None


# -- idle time by what the scheduler was doing -------------------------------

def busy_intervals(trace) -> List[Tuple[float, float]]:
    """Merged intervals in which an operation ran on the first device
    plane."""
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    return trace_reduce.union((o[0], o[1]) for o in ops)


def idle_intervals(trace) -> List[Tuple[float, float]]:
    """Intervals of the slice in which no operation ran on the first device
    plane."""
    t0, t1 = trace.window()
    busy = busy_intervals(trace)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def overlap(intervals, others) -> float:
    """Seconds of ``intervals`` covered by ``others`` (both merged first)."""
    a, b = trace_reduce.union(intervals), trace_reduce.union(others)
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def innermost(shifted_rows, t0: float, t1: float
              ) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted ``(start, end, name)`` over ``[t0, t1]``: at every
    moment the span that started last among those open (a thread's spans
    nest, so that is its innermost)."""
    live = sorted((r for r in shifted_rows if r[END] > t0 and r[START] < t1),
                  key=lambda r: r[START])
    cuts = sorted({t0, t1} | {x for r in live for x in (r[START], r[END])
                              if t0 < x < t1})
    out, nxt, open_ = [], 0, []
    for lo, hi in zip(cuts, cuts[1:]):
        while nxt < len(live) and live[nxt][START] <= lo:
            open_.append(live[nxt])
            nxt += 1
        open_ = [r for r in open_ if r[END] > lo]
        if open_:
            out.append((lo, hi, open_[-1][NAME]))
    return out


def idle_by_span(trace, shifted_rows) -> Dict[str, float]:
    """Idle seconds of the slice by the innermost program span open at the
    time (rows already on the trace's clock); ``no_span`` = outside all."""
    t0, t1 = trace.window()
    idle = idle_intervals(trace)
    out: Dict[str, float] = {}
    j = 0
    for s, e, name in innermost(shifted_rows, t0, t1):
        while j < len(idle) and idle[j][1] <= s:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < e:
            out[name] = out.get(name, 0.0) \
                + min(e, idle[k][1]) - max(s, idle[k][0])
            k += 1
    rest = sum(b - a for a, b in idle) - sum(out.values())
    if rest > 0:
        out["no_span"] = rest
    return out

"""Device time of a run's traced segment by the program's kernel scope.

The program names its kernels with ``jax.named_scope``
(``repro.obs.tracing.KERNEL_SCOPES``), and a device op belongs to the
outermost kernel scope on its ``op_name`` path.  A TPU trace names an
op by its HLO instruction alone (``%fusion.47 = f32[...] fusion(...)``,
no ``op_name``), so ops are put down to scopes through the program's
scope tables (``repro.obs.tracing.scope_tables``), read in-process
after the window: each maps the instructions of one compiled program to
their scope.  The trace names a program run ``<module>(<program id>)``,
an id the executable does not expose, so the ops under each run name
are matched to the tables of that module whose instructions agree with
theirs (name; result shape and opcode where the trace prints them).  An
op that no table maps, or that two matching tables map differently, is
counted as unmatched, never guessed.

A program without scope tables (one older than them) reads None.
"""
from __future__ import annotations

import bisect
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional

from bench import harness, trace_reduce

NONE, UNMATCHED = "(none)", "(unmatched)"
# the programs whose busy time the kernel scopes should cover: the
# engine's step and the direct solve
SOLVE_PROGRAMS = ("jit_step", "jit_run")


def program_tables() -> Optional[Dict]:
    """The program's scope tables, or None where it keeps none."""
    try:
        from repro.obs.tracing import scope_tables
    except ImportError:
        return None
    return scope_tables() or None


def _scoper(tables: Dict, prog: str, names):
    """A function from an op's event name to its scope, for the ops of
    the program runs called ``prog``."""
    from repro.obs.tracing import instruction_signature
    module = trace_reduce.program_name(prog)
    seen = {instruction_signature(n) for n in names}
    fits = [t for (mod, _), t in tables.items() if mod == module
            and all(i in t and (not sig or t[i][0] == sig)
                    for i, sig in seen)]

    def scope(name: str) -> str:
        i, _ = instruction_signature(name)
        got = {t[i][1] for t in fits}
        if len(got) != 1:
            return UNMATCHED
        return got.pop() or NONE
    return scope


def _ops_by_program(tr, lo, hi):
    """Each program run name → its ops' ``(name, start, end)``, clipped
    to ``[lo, hi]``; an op belongs to the run that contains its start
    (as ``trace_reduce.top_ops``).  Ops that hold others (a loop, a
    branch) are kept: the time a scoped loop spends between its
    children's ops is that scope's too."""
    runs = sorted(tr.modules, key=lambda m: m[1])
    starts = [m[1] for m in runs]
    out = defaultdict(list)
    for name, s, e in tr.ops:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 <= s2:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = runs[i][0] if i >= 0 and runs[i][2] >= s else "?"
        out[prog].append((name, s2, e2))
    return out


def by_scope(run) -> Optional[Dict]:
    """``{"intervals": {scope: union of op intervals}, "ops":
    {"program/op": scope}, "covered": share, "outside_s": seconds,
    "tables": count, "tables_s": seconds}`` for the traced segment,
    where ``covered`` is the share of the solve programs' op time that
    lies under a kernel scope, ``outside_s`` the op time of every
    program under none, and ``tables_s`` the time the scope tables took
    to read; None where there is nothing to read.  Computed once per
    run, and reported once on stderr."""
    if "scopes" not in run.segment:
        run.segment["scopes"] = got = _by_scope(run)
        if got is not None:
            print(f"bench: {report(run, got)}", file=sys.stderr, flush=True)
    return run.segment["scopes"]


def _by_scope(run) -> Optional[Dict]:
    tr = run.window_trace
    bounds = harness.segment_bounds(tr)
    if bounds is None or not tr.devices:
        return None
    t0 = time.perf_counter()
    tables = program_tables()
    if tables is None:
        return None
    tables_s = time.perf_counter() - t0
    iv, ops = defaultdict(list), {}
    every, scoped = defaultdict(list), defaultdict(list)
    for prog, evs in _ops_by_program(tr, *bounds).items():
        scope = _scoper(tables, prog, {n for n, _, _ in evs})
        short = trace_reduce.program_name(prog)
        solve = short in SOLVE_PROGRAMS
        for name, s, e in evs:
            sc = scope(name)
            iv[sc].append((s, e))
            key = f"{short}/{trace_reduce.op_name(name)}"
            ops[key] = sc if ops.get(key, sc) == sc else UNMATCHED
            every[solve].append((s, e))
            if sc not in (NONE, UNMATCHED):
                scoped[solve].append((s, e))
    tot = _ns(every[True])
    return {"intervals": {k: trace_reduce.union(v) for k, v in iv.items()},
            "ops": ops,
            "covered": _ns(scoped[True]) / tot if tot else None,
            "outside_s": (_ns(every[True] + every[False])
                          - _ns(scoped[True] + scoped[False])) * 1e-9,
            "tables": len(tables), "tables_s": tables_s}


def _ns(intervals) -> float:
    return sum(e - s for s, e in trace_reduce.union(intervals))


def seconds(run, scope: str) -> Optional[float]:
    """Device seconds of the traced segment under ``scope``."""
    got = by_scope(run)
    if got is None:
        return None
    return _ns(got["intervals"].get(scope, [])) * 1e-9


def report(run, got: Dict) -> str:
    tr = run.window_trace
    secs = {k: _ns(v) * 1e-9 for k, v in got["intervals"].items()
            if k != NONE}
    top = trace_reduce.top_ops(tr, *harness.segment_bounds(tr))
    return (f"scope tables of {got['tables']} programs, read in "
            f"{got['tables_s']!r} s; segment device seconds by kernel scope "
            + ", ".join(f"{k} {v!r}" for k, v in sorted(secs.items()))
            + f", outside every scope {got['outside_s']!r}"
            + f"; share of {'/'.join(SOLVE_PROGRAMS)} op time under a "
            f"kernel scope {got['covered']!r}; top ops: "
            + ", ".join(f"{op} {got['ops'].get(op, UNMATCHED)}"
                        for op, _ in top))


def construction(run) -> Optional[Dict[str, float]]:
    """Wall seconds of each program ``construct/…`` stage inside the
    traced cold start's ``cold_start/factor`` span, with the span's own
    length (``"span"``) and the share the stages cover (``"covered"``);
    None where the trace has no such stage.  Reported once on stderr."""
    if "construction" in run.segment:
        return run.segment["construction"]
    tr = run.cold_trace
    span = None if tr is None else trace_reduce.span_named(
        tr, "cold_start/factor")
    got = None
    stages = [] if span is None else [
        (n, s, e) for n, s, e in tr.spans
        if n.startswith("construct/") and span[0] <= s and e <= span[1]]
    if stages:
        got = defaultdict(float)
        for n, s, e in stages:
            got[n] += (e - s) * 1e-9
        got["span"] = (span[1] - span[0]) * 1e-9
        got["covered"] = _ns([(s, e) for _, s, e in stages]) * 1e-9 \
            / got["span"]
        got = dict(got)
        print("bench: cold start's construction stages (s): "
              + ", ".join(f"{k} {v!r}" for k, v in got.items()),
              file=sys.stderr, flush=True)
    run.segment["construction"] = got
    return got

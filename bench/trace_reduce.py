"""Reduce a JAX profiler trace to device busy and idle time.

A trace (the ``.xplane.pb`` that ``jax.profiler`` writes) is read with
``jax.profiler.ProfileData`` into three lists: device operations (the
``XLA Ops`` lines of the ``/device:`` planes), device program runs (the
``XLA Modules`` lines) and the benchmark's own host spans (its
``jax.profiler.TraceAnnotation`` names, all of which contain a ``/``).
All times are nanoseconds on the trace's one clock.

Busy time is the union of the operation and program-run intervals;
idle time is the rest of a span.  Each idle gap is named by the host span that overlaps
it most (the innermost on a tie); a span whose name ends in
``/await`` only says that the client was waiting for the service, so
it names nothing, and such gaps are "unattributed".
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)


def is_device_op_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:") and line == "XLA Ops"


def is_device_module_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:") and line == "XLA Modules"


@dataclass
class Trace:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    devices: int = 0


def find_xplane(trace_dir: str) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def load(path, *, op_line: Optional[Callable[[str, str], bool]] = None,
         module_line: Optional[Callable[[str, str], bool]] = None
         ) -> Trace:
    """Read the events of one ``.xplane.pb`` file.  ``op_line`` and
    ``module_line`` say which (plane, line) pairs hold device
    operations and program runs (default: the device planes' ``XLA
    Ops`` and ``XLA Modules`` lines)."""
    from jax.profiler import ProfileData
    op_line = op_line or is_device_op_line
    module_line = module_line or is_device_module_line
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    device_planes = set()
    for plane in pd.planes:
        for line in plane.lines:
            is_op = op_line(plane.name, line.name)
            is_mod = module_line(plane.name, line.name)
            host = plane.name.startswith("/host:")
            if not (is_op or is_mod or host):
                continue
            for ev in line.events:
                name = ev.name
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if is_op or is_mod:
                    (tr.ops if is_op else tr.modules).append(
                        (name, start, start + dur))
                    device_planes.add(plane.name)
                elif "/" in name and dur > 0 and not name.startswith("$"):
                    tr.spans.append((name, start, start + dur))
    tr.devices = len(device_planes)
    return tr


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _busy(tr: Trace) -> List[Interval]:
    return union([(s, e) for _, s, e in tr.ops + tr.modules])


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Nanoseconds in ``[lo, hi]`` in which the device ran something."""
    return sum(e - s for s, e in clip(_busy(tr), lo, hi))


def gaps(tr: Trace, lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]``."""
    out, at = [], lo
    for s, e in clip(_busy(tr), lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def span_named(tr: Trace, name: str) -> Optional[Interval]:
    """The first host span called ``name``."""
    for n, s, e in tr.spans:
        if n == name:
            return (s, e)
    return None


def name_gap(tr: Trace, gap: Interval) -> str:
    best, key = "unattributed", (0.0, 0.0)
    for n, s, e in tr.spans:
        if n.endswith("/await"):
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > key:
            best, key = n, (ov, -(e - s))
    return best


def program_name(name: str) -> str:
    """A program run's name without its fingerprint: ``jit_run(123)`` →
    ``jit_run``."""
    return name.split("(", 1)[0]


def op_name(name: str) -> str:
    """A device op's HLO instruction name: a TPU names its op events by
    the whole instruction (``%fusion.47 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0]


# ops that hold other ops: their time is their children's
CONTAINERS = ("%while", "%cond", "%call")


def top_ops(tr: Trace, lo: float, hi: float, k: int = 10
            ) -> List[Tuple[str, float]]:
    """Device seconds by program and op name (``program/op``), most
    first, leaving out ops that contain others; an op counts under the
    program run that contains its start."""
    runs = sorted(tr.modules, key=lambda m: m[1])
    starts = [m[1] for m in runs]
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in tr.ops:
        s2, e2 = max(s, lo), min(e, hi)
        op = op_name(name)
        if e2 <= s2 or op.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = program_name(runs[i][0]) if i >= 0 and runs[i][2] >= s \
            else "?"
        tot[f"{prog}/{op}"] += (e2 - s2) * 1e-9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def top_gaps(tr: Trace, lo: float, hi: float, k: int = 10
             ) -> List[Tuple[str, float]]:
    """The ``k`` longest idle gaps of ``[lo, hi]``, each named."""
    gs = sorted(gaps(tr, lo, hi), key=lambda g: g[0] - g[1])[:k]
    return [(name_gap(tr, g), (g[1] - g[0]) * 1e-9) for g in gs]

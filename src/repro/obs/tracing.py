"""Tracing: per-request lifecycle spans, and in-program spans and
kernel scopes on the profiler's clock.

**Request lifecycle.**  A request's wall-clock decomposes into a
contiguous partition of ``[submit_time, finish_time]``::

    route          submit .. +route_s          router decision + retries
    factor|adopt   .. +factor_wait_s           cold-path construction wait
    queue          .. admit_time               admission queue (head block)
    first_tick     admit .. first_tick_time    scatter-in + first step call
    solve          first_tick .. finish_time   PCG ticks to convergence

Stages a request never paid (warm hit -> no factor span; engine
recorded no first tick -> solve covers admit..finish) collapse to
nothing rather than to zero-length lies, and because the partition is
contiguous the span durations sum to the reported e2e latency exactly
— the acceptance bound (<= 5%) only absorbs float rounding.

Spans come from stamps the serving layers already cross on the host
side (`SolveRequest.submit_time` / `admit_time` / `finish_time` plus
the new ``route_s`` / ``factor_wait_s`` / ``first_tick_time``), so
tracing adds no device syncs; the engine stamps first ticks only when
a tracer is attached.

Export is Chrome ``trace_event`` JSON (``{"traceEvents": [...]}``,
complete events ``ph="X"``, microsecond ``ts``/``dur``) — loads
directly in ``chrome://tracing`` / Perfetto.  ``pid`` is the replica
(one track group per replica), ``tid`` is the request id (one row per
request), so a request's spans nest on their own row and cross-replica
interleaving is visible at a glance.

**Program spans** (:data:`PROGRAM_SPANS`, entered with :func:`span`)
are profiler annotations (``jax.profiler.TraceAnnotation``) around
the host phases of the serving path and of construction: they land in
a profiler trace on the same clock as the device's op rows, so an idle
gap on the device can be put down to what the host was doing.  With no
profiler session running a span costs one enabled-check; it never
syncs with the device, so it ends where its host code ends and the
device time under it is read off the trace.

**Kernel scopes** (:data:`KERNEL_SCOPES`) are ``jax.named_scope``
names on the solve kernels: compiled in, they name each HLO
instruction's ``op_name``.  A device op belongs to the outermost kernel
scope on its ``op_name`` path (a trisolve sweep's SpMV is trisolve
time).  A TPU trace names an op by its HLO instruction alone, so while
a profiler session runs the programs that ran are noted
(:func:`note_program`), and :func:`scope_tables` maps each one's
instructions to their scopes, from the executable JAX already holds.
"""
from __future__ import annotations

import json
import re
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

# The lifecycle stages, in partition order.
STAGES = ("route", "factor", "adopt", "queue", "first_tick", "solve")

# Host spans of the program, as they nest: an engine tick and its
# phases; the frontend driver's control calls and submissions;
# ``PreconditionerHandle.solve``'s dispatch; inside a step's or a
# solve's, the fleet program's call by index path
# (``FactorFleet.index_path``); construction's stages.
PROGRAM_SPANS = (
    "engine/tick", "engine/admit", "engine/step", "engine/readback",
    "engine/retire",
    "frontend/control", "frontend/submit",
    "solver/solve",
    "fleet/shared", "fleet/per_lane",
    "construct/pool", "construct/eliminate", "construct/finalize",
    "construct/schedules", "construct/pack", "construct/admit",
)

# Device-side kernel scopes (``jax.named_scope`` names).
KERNEL_SCOPES = ("trisolve_fleet", "ell_spmv_fleet", "fleet_matvec",
                 "pcg_update")


def span(name: str) -> TraceAnnotation:
    """A program span: ``with span("engine/step"): ...``.  ``name`` is
    one of :data:`PROGRAM_SPANS`."""
    return TraceAnnotation(name)


@dataclass(frozen=True)
class Span:
    """One contiguous stage of a request's lifetime, in the engine
    clock's coordinates (seconds)."""
    name: str
    start: float
    end: float

    @property
    def dur_s(self) -> float:
        return max(self.end - self.start, 0.0)


@dataclass
class RequestTrace:
    """The full lifecycle record for one retired request."""
    rid: int
    graph_id: str
    family: str = ""
    policy: str = ""
    status: str = ""
    replica: int = -1
    device: str = ""
    trace_id: str = ""
    spans: List[Span] = field(default_factory=list)
    attrs: Dict[str, object] = field(default_factory=dict)

    @property
    def start(self) -> float:
        return self.spans[0].start if self.spans else 0.0

    @property
    def end(self) -> float:
        return self.spans[-1].end if self.spans else 0.0

    @property
    def e2e_s(self) -> float:
        return max(self.end - self.start, 0.0)

    @property
    def span_sum_s(self) -> float:
        return sum(s.dur_s for s in self.spans)


def trace_from_request(req, *, family: str = "", policy: str = "",
                       replica: int = -1,
                       device: str = "") -> Optional[RequestTrace]:
    """Build a :class:`RequestTrace` from a retired
    :class:`~repro.serve.engine.SolveRequest`'s host-side stamps.
    Returns ``None`` if the request never finished (no partition to
    report)."""
    if req.finish_time <= 0.0 or req.submit_time <= 0.0:
        return None
    t = req.submit_time
    end = req.finish_time
    spans: List[Span] = []

    def push(name: str, lo: float, hi: float) -> float:
        hi = min(max(hi, lo), end)
        if hi > lo:
            spans.append(Span(name, lo, hi))
        return hi

    route_s = getattr(req, "route_s", 0.0)
    factor_s = getattr(req, "factor_wait_s", 0.0)
    mode = getattr(req, "factor_mode", "") or "factor"
    first = getattr(req, "first_tick_time", 0.0)
    admit = req.admit_time if req.admit_time > 0.0 else t

    cur = push("route", t, t + route_s)
    cur = push("adopt" if mode == "adopt" else "factor", cur, cur + factor_s)
    cur = push("queue", cur, max(admit, cur))
    if first > cur:
        cur = push("first_tick", cur, first)
    push("solve", cur, end)

    iters = req.iters
    max_iters = int(max(iters)) if iters is not None and len(iters) else 0
    if replica < 0:
        replica = getattr(req, "replica", -1)
    return RequestTrace(
        rid=req.rid, graph_id=req.graph_id, family=family,
        policy=policy, status=req.status, replica=replica, device=device,
        trace_id=getattr(req, "trace_id", ""),
        spans=spans,
        attrs={"iters": max_iters, "nrhs": req.nrhs,
               "factor_mode": getattr(req, "factor_mode", "") or ""})


class Tracer:
    """Thread-safe bounded sink of :class:`RequestTrace` records.

    Layers that can emit a trace take ``tracer=None`` and call
    :meth:`record` only when one is attached; the deque bound keeps a
    long replay from hoarding host memory (the oldest traces fall off).
    """

    def __init__(self, *, capacity: int = 8192):
        self._lock = threading.Lock()
        self._traces: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._seen = 0

    def record(self, trace: Optional[RequestTrace]) -> None:
        if trace is None:
            return
        with self._lock:
            if len(self._traces) == self._traces.maxlen:
                self.dropped += 1
            self._traces.append(trace)
            self._seen += 1

    def traces(self) -> List[RequestTrace]:
        with self._lock:
            return list(self._traces)

    def __len__(self) -> int:
        return len(self._traces)

    # -- Chrome trace_event export -----------------------------------------
    def chrome_events(self) -> List[Dict]:
        """Complete events (``ph="X"``) with µs timestamps relative to
        the earliest span — pid=replica, tid=request id, so spans nest
        per request row under per-replica track groups."""
        traces = self.traces()
        if not traces:
            return []
        t0 = min(tr.start for tr in traces if tr.spans)
        events: List[Dict] = []
        named: set = set()
        for tr in traces:
            pid = tr.replica if tr.replica >= 0 else 0
            if pid not in named:
                named.add(pid)
                events.append({
                    "name": "process_name", "ph": "M", "pid": pid,
                    "args": {"name": f"replica {pid}" if tr.replica >= 0
                             else "engine"}})
            for sp in tr.spans:
                events.append({
                    "name": sp.name, "ph": "X", "cat": "request",
                    "pid": pid, "tid": tr.rid,
                    "ts": (sp.start - t0) * 1e6,
                    "dur": sp.dur_s * 1e6,
                    "args": {"rid": tr.rid, "graph_id": tr.graph_id,
                             "trace_id": tr.trace_id,
                             "family": tr.family, "policy": tr.policy,
                             "status": tr.status, "device": tr.device,
                             **tr.attrs}})
        return events

    def export_chrome(self, path: str) -> int:
        """Write ``{"traceEvents": [...]}`` JSON; returns the event
        count (0 writes an empty-but-valid file)."""
        events = self.chrome_events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return len(events)

    # -- aggregate reads ----------------------------------------------------
    def stage_seconds(self) -> Dict[str, float]:
        """Total seconds spent per stage across recorded traces — the
        construct-vs-serve attribution the selector and reports read."""
        out: Dict[str, float] = {}
        for tr in self.traces():
            for sp in tr.spans:
                out[sp.name] = out.get(sp.name, 0.0) + sp.dur_s
        return out

    def stats(self) -> Dict[str, object]:
        with self._lock:
            n, dropped = len(self._traces), self.dropped
            seen = self._seen
        return {"recorded": n, "seen": seen, "dropped": dropped,
                "stage_s": self.stage_seconds()}


# -- kernel scopes of compiled programs -------------------------------------

def _spec(x):
    """An argument as ``jit.lower`` sees it: arrays become shapes (with
    the device of a committed array, so the lowering finds the same
    executable), everything else stays."""
    import jax
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x.committed else None)
    if isinstance(x, (np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


_notes_lock = threading.Lock()
_noted: set = set()                      # programs noted so far
_todo: List[Tuple] = []                  # (fn, args, kwargs) to table
_tables: Dict[Tuple[str, str], Dict[str, Tuple[str, Optional[str]]]] = {}


def note_program(fn, *args, **kwargs) -> None:
    """Note that the jitted ``fn`` just ran on ``args``/``kwargs``
    (static arguments by keyword), while a profiler session runs; a
    no-op otherwise.  Keeps shapes, not arrays."""
    if not TraceAnnotation.is_enabled():
        return
    import jax
    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    specs = [_spec(x) for x in leaves]
    key = (fn, tree, tuple(
        (s.shape, str(s.dtype), s.sharding is not None)
        if isinstance(s, jax.ShapeDtypeStruct) else repr(s)
        for s in specs))
    with _notes_lock:
        if key not in _noted:
            _noted.add(key)
            _todo.append((fn, *jax.tree_util.tree_unflatten(tree, specs)))


def scope_of(op_name: str) -> Optional[str]:
    """The outermost kernel scope on an ``op_name`` path, or None."""
    for part in op_name.split("/"):
        if part in KERNEL_SCOPES:
            return part
    return None


def instruction_signature(text: str) -> Tuple[str, str]:
    """``(name, "shape opcode")`` of one HLO instruction as HLO text or
    a TPU trace prints it (``%fusion.4 = f32[8]{0} fusion(...)``), the
    shape without its layout; the signature is empty where the text is
    a bare instruction name."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, eq, rest = text.partition(" = ")
    name = name.lstrip("%")
    if not eq:
        return name, ""
    if rest.startswith("("):               # a tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    shape = re.sub(r"\{[^{}]*\}", "", shape)           # layouts
    return name, f"{shape} {rest.split('(', 1)[0]}"


def _op_name(line: str) -> str:
    at = line.find('op_name="')
    if at < 0:
        return ""
    at += len('op_name="')
    return line[at:line.index('"', at)]


_CALLED = re.compile(r"(?:condition|body|calls|to_apply|true_computation"
                     r"|false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def parse_scope_table(hlo_text: str) -> Tuple[str, Dict]:
    """``(module name, {instruction: (signature, scope)})`` of a
    compiled module's HLO text.  The compiler's rewrites (a gather
    expanded into a loop, a scatter fused with its index math) leave
    instructions with a bare or empty ``op_name``, so an instruction
    inside a computation that a scoped instruction calls (a loop body,
    a branch, a fusion) takes that outer scope, and one with no scope
    of its own takes the one scope of what it calls, where that is
    one."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    comp, found, callers, members = "", {}, {}, {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if line.endswith("{") and " = " not in line.split("(", 1)[0]:
            comp = line.replace("ENTRY ", "").split(None, 1)[0].lstrip("%")
            continue
        if not line.startswith(("%", "ROOT %")) or " = " not in line:
            continue
        name, sig = instruction_signature(line)
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        found[name] = (comp, sig, scope_of(_op_name(line)), called)
        members.setdefault(comp, []).append(name)
        for c in called:
            callers.setdefault(c, set()).add(name)
    outer: Dict[str, Optional[str]] = {}
    inner: Dict[str, set] = {}

    def one(scopes) -> Optional[str]:
        scopes = set(scopes) - {None}
        return scopes.pop() if len(scopes) == 1 else None

    def scopes_in(c: str) -> set:
        # every scope named inside computation c, however deep
        if c not in inner:
            inner[c] = set()
            for i in members.get(c, ()):
                inner[c] |= {found[i][2]}.union(
                    *(scopes_in(d) for d in found[i][3]))
        return inner[c]

    def scope_around(c: str) -> Optional[str]:
        # the scope computation c runs under: its callers', if one
        if c not in outer:
            outer[c] = None                    # a cycle reads no scope
            outer[c] = one(resolved(i) for i in callers.get(c, ()))
        return outer[c]

    def resolved(i: str) -> Optional[str]:
        c, _, own, called = found[i]
        return scope_around(c) or own or one(
            set().union(*(scopes_in(d) for d in called)))

    return module, {i: (f[1], resolved(i)) for i, f in found.items()}


def scope_tables() -> Dict[Tuple[str, str], Dict]:
    """Kernel-scope tables of every program noted so far, keyed by
    ``(module name, executable fingerprint)``: each maps an HLO
    instruction name to its signature (``"shape opcode"``) and its
    kernel scope (None outside every scope).  Built on first read from
    the executables JAX holds (``lower(...).compile()`` finds them in
    its cache: no backend compile), so a call pays nothing for it."""
    with _notes_lock:
        todo = list(_todo)
        _todo.clear()
    for fn, args, kwargs in todo:
        compiled = fn.lower(*args, **kwargs).compile()
        module, table = parse_scope_table(compiled.as_text())
        fp = compiled.runtime_executable().fingerprint
        fp = fp.hex() if isinstance(fp, bytes) else str(fp)
        _tables[(module, fp)] = table
    return dict(_tables)

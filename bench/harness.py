"""One run of one cell: set-up, cold start, warm-up, the measured
window, the check against the reference, and the metrics.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell's configuration file and traffic mix, a configuration names its
graph generator (``generators/<name>.py``), and each metric is read by
``metrics/<name>.py``.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import lanes, loadgen, trace_reduce, work
from bench.reference import EdgeGraph, host_relres

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CONFIG_KEYS = {"generator", "params", "ordering", "ordering_seed", "family",
               "fill_slack", "construction_key", "slots", "iters_per_tick",
               "tol", "maxiter", "check", "source", "reduced", "assumed",
               "deployment", "n", "m"}
# share of the window after which the traced segment starts, and its
# length in seconds
SEGMENT_AT = 0.3
SEGMENT_S = 3.0


class NoAccelerator(RuntimeError):
    pass


class ColdStartCompiled(RuntimeError):
    """The cold start compiled programs (a checkout's first run), and
    the session was asked to stop there: a fresh process measures it
    again, finding them in the persistent cache."""


def configure_jax() -> None:
    """The persistent compile cache at a fixed directory inside the
    checkout (the program's cache takes the one given here), keeping
    every program from a cell's first run on (no time or size floor, no
    eviction: a cell's cache is tens of MB, and eviction races between
    compiling threads lose entries)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.launch import compile_cache
    compile_cache.enable()


@dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    mix: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_config(path) -> Dict:
    cfg = json.loads(Path(path).read_text())
    unknown = set(cfg) - CONFIG_KEYS
    if unknown:
        raise ValueError(f"{path}: unknown configuration keys "
                         f"{sorted(unknown)}")
    return cfg


def load_cell(workload: str, spec_path: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {spec_path.name} "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, config_name=w["config"],
        config=load_config(spec_path.parent / conf["file"]),
        mix=loadgen.load_mix(HERE / "traffic" / f"{w['traffic']}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def make_graph(cfg: Dict) -> EdgeGraph:
    gen = _load_module(HERE / "generators" / f"{cfg['generator']}.py")
    g = EdgeGraph(*gen.generate(**cfg["params"]))
    if (g.n, g.m) != (cfg["n"], cfg["m"]):
        raise ValueError(f"generator {cfg['generator']} made n={g.n} "
                         f"m={g.m}, the configuration states n={cfg['n']} "
                         f"m={cfg['m']}")
    return g


class CompileCount:
    """Programs JAX asked the compiler or the persistent cache for
    while the block ran: ``requested`` of them, ``loaded`` from the
    cache, the rest compiled, in ``compile_s`` seconds.  (A cache hit
    is recorded just before its request's duration.)"""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __enter__(self):
        import jax.monitoring as mon
        self.requested = self.loaded = 0
        self.compile_s = 0.0
        hit = [False]

        def on_duration(event, duration, **kw):
            if event == self._COMPILE:
                self.requested += 1
                if not hit[0]:
                    self.compile_s += duration
                hit[0] = False

        def on_event(event, **kw):
            if event == self._HIT:
                self.loaded += 1
                hit[0] = True

        self._cbs = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._cbs[0])
        mon.unregister_event_listener(self._cbs[1])

    @property
    def compiled(self) -> int:
        return self.requested - self.loaded


def _cpu_jiffies() -> Optional[Dict[str, float]]:
    """The host's steal and iowait seconds over all cores so far
    (``/proc/stat``), or None where it cannot be read."""
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        hz = os.sysconf("SC_CLK_TCK")
        return {"iowait": int(cpu[5]) / hz, "steal": int(cpu[8]) / hz}
    except (OSError, IndexError, ValueError):
        return None


class HostWatch:
    """What the host did while a block ran, to tell a slow run's cause:
    the wall seconds of named phases, the garbage collector's pauses,
    and the host's steal and iowait seconds (all cores)."""

    def __init__(self):
        self.phases: Dict[str, float] = {}
        self.gc_pauses: List[float] = []
        self.gc_full = 0

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = time.perf_counter() - t0

    def _on_gc(self, what, info):
        if what == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)
            self.gc_full += info.get("generation") == 2

    def __enter__(self):
        self._cpu0 = _cpu_jiffies()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        cpu1 = _cpu_jiffies()
        self.cpu = None if self._cpu0 is None or cpu1 is None else {
            k: cpu1[k] - self._cpu0[k] for k in cpu1}

    def report(self) -> str:
        parts = [f"{k} {v:.3f} s" for k, v in self.phases.items()]
        p = self.gc_pauses
        parts.append(f"gc {len(p)} collections ({self.gc_full} full), "
                     f"longest {max(p, default=0.0):.3f} s, total "
                     f"{sum(p):.3f} s")
        if self.cpu is not None:
            parts.append(f"host steal {self.cpu['steal']:.2f} s, iowait "
                         f"{self.cpu['iowait']:.2f} s (all cores)")
        return "; ".join(parts)


@dataclass
class Run:
    """What one run measured; the metric readers read this."""
    cell: Cell
    seed: int
    graph: EdgeGraph
    device_kind: str = ""
    setup_s: float = 0.0
    cold_start_s: float = 0.0
    cold_compiles: Optional[CompileCount] = None
    window_compiles: Optional[CompileCount] = None
    window_t0: float = 0.0
    first: Optional[loadgen.Sent] = None
    sent: List[loadgen.Sent] = field(default_factory=list)
    nnz: int = 0
    sweeps_per_apply: Optional[int] = None
    memory_peak_bytes: Optional[int] = None
    cold_trace: Optional[trace_reduce.Trace] = None
    window_trace: Optional[trace_reduce.Trace] = None
    segment: Dict = field(default_factory=dict)
    failed: int = 0

    @property
    def done(self) -> List[loadgen.Sent]:
        return [r for r in self.sent if r.t_done is not None]

    @property
    def converged(self) -> List[loadgen.Sent]:
        return [r for r in self.sent if r.status == "converged"]

    @property
    def factor_work(self):
        """``(nnz, n, m)`` of the one factor this cell serves."""
        return (self.nnz, self.graph.n, self.graph.m)


def _devices(require_tpu: bool, chips: int):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU found (JAX sees {len(devs)} "
                            f"{devs[0].platform} device(s))")
    if require_tpu and len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds "
                            f"{len(devs)}")
    return devs


def _sweeps_per_apply(h) -> int:
    """Sweeps of one forward plus one backward apply of ``h``'s factor,
    from its sweep plan as the fleet stores it."""
    fl, row = h.fleet, h.fleet_row
    a = fl.arrays

    def side(ext, gend, ptr, nlv, cols, width):
        return work.trisolve_sweeps(
            np.asarray(ext[row]), np.asarray(gend[row]),
            np.asarray(ptr[row]), int(nlv[row]), int(cols.shape[2]), width)

    return (side(a.fext, a.fgend, a.fptr, a.fnlv, a.fcols, fl.f_width)
            + side(a.bext, a.bgend, a.bptr, a.bnlv, a.bcols, fl.b_width))


class _Tracer:
    """One profiler session into a fresh directory under TMPDIR."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        # no Python call tracing: the benchmark's spans are annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self) -> trace_reduce.Trace:
        try:
            path = trace_reduce.find_xplane(self.dir)
            return trace_reduce.load(path) if path else trace_reduce.Trace()
        finally:
            self.discard()

    def discard(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _mark(name: str) -> None:
    import jax
    with jax.profiler.TraceAnnotation(name):
        time.sleep(1e-4)


class Session:
    """One process's service for one cell: the graph, its cold start
    (ordering, ``FactorCache.factor``, the first solve on the cell's
    path), the warm-up, and then any number of measured windows."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool = False,
                 require_tpu: bool = True, grace_s: float = 60.0,
                 exit_if_compiled: bool = False, log=print):
        self.devs = _devices(require_tpu, cell.chips)
        self.cell, self.grace_s, self.log = cell, grace_s, log
        self.exit_if_compiled = exit_if_compiled
        cfg = cell.config
        self.graph = make_graph(cfg)
        self.tol, self.maxiter = float(cfg["tol"]), int(cfg["maxiter"])
        self.served = cell.mix["path"] == "served"
        self.fe = self.engine = self.h = self.cache = None
        self.perm = None
        self.cold_trace = None
        self._cold_start(seed, trace)

    # -- the program's view of an answer, in the graph's original labels --
    def _to_program(self, b):
        out = np.empty_like(b)
        out[..., self.perm] = b
        return out

    def _collect_served(self, rec, req):
        rec.x = np.asarray(req.x)[..., self.perm]
        rec.iters, rec.relres = np.asarray(req.iters), np.asarray(req.relres)
        rec.status = req.status
        rec.admit_tick, rec.finish_tick = req.admit_tick, req.finish_tick

    def _collect_direct(self, rec, res):
        import jax
        res = jax.device_get(res)
        rec.x = np.asarray(res.x)[..., self.perm]
        rec.iters, rec.relres = np.asarray(res.iters), np.asarray(res.relres)
        rec.status = "converged" if bool(np.all(res.converged)) \
            else "maxiter"

    def _submit(self, b):
        return self.fe.submit(self.cell.config_name, self._to_program(b),
                              tol=self.tol, maxiter=self.maxiter)

    def _solve(self, b):
        return self.h.solve(self._to_program(b), tol=self.tol,
                            maxiter=self.maxiter)

    # -- set-up -----------------------------------------------------------
    def _cold_start(self, seed: int, trace: bool) -> None:
        """Graph in host memory → the first answer on the host, with
        programs from the persistent cache, as a freshly started service
        finds them."""
        cc, first, elapsed, tracer = self._cold_pass(seed, trace)
        if cc.compiled and self.exit_if_compiled:
            if tracer:
                tracer.discard()
            self.close()
            raise ColdStartCompiled(
                f"cold start compiled {cc.compiled} programs (in "
                f"{cc.compile_s!r} s, {elapsed!r} s in all; set-up)")
        tr = None
        if tracer:
            t1 = time.perf_counter()
            tr = tracer.load()
            self.log(f"cold-start trace: {len(tr.ops)} device ops, "
                     f"{len(tr.modules)} program runs, read in "
                     f"{time.perf_counter() - t1!r} s")
        # a program compiled here is set-up, not cold start
        self.cold_start_s = elapsed - cc.compile_s
        self.cold_compiles, self.first, self.cold_trace = cc, first, tr
        self.log(f"cold start: {self.cold_start_s!r} s; programs compiled "
                 f"{cc.compiled} (in {cc.compile_s!r} s, not counted), "
                 f"loaded from the persistent cache {cc.loaded}; "
                 + self._phases.report())
        self.nnz = int(self.h.factor.nnz)
        self.sweeps_per_apply = _sweeps_per_apply(self.h) \
            if self.h.kind == "factor" else None

    def _cold_pass(self, seed: int, trace: bool):
        """One cold start; returns ``(compile count, first answer,
        seconds, its stopped tracer or None)``."""
        import jax
        from repro.core.laplacian import Graph
        from repro.core.ordering import ORDERINGS
        from repro.core.solver import FactorCache
        cfg, g = self.cell.config, self.graph
        graph_in = Graph(g.n, g.src.copy(), g.dst.copy(), g.w.copy())
        tracer = _Tracer() if trace else None
        if tracer:
            tracer.start()
        b0 = loadgen.rhs(seed, g.n, 1, 1)
        ph = self._phases = HostWatch()
        with CompileCount() as cc, ph:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("cold_start/order"), \
                    ph.phase("order"):
                perm = np.asarray(ORDERINGS[cfg["ordering"]](
                    graph_in, seed=int(cfg["ordering_seed"])))
                if not np.array_equal(np.sort(perm), np.arange(g.n)):
                    raise RuntimeError("the ordering is not a permutation")
                self.perm = perm
                gp = graph_in.permute(perm).coalesce()
            with jax.profiler.TraceAnnotation("cold_start/factor"), \
                    ph.phase("factor"):
                self.cache = FactorCache(fill_slack=int(cfg["fill_slack"]))
                self.h = self.cache.factor(
                    gp, jax.random.key(int(cfg["construction_key"])),
                    graph_id=self.cell.config_name, family=cfg["family"])
                jax.block_until_ready(self.h.fleet.arrays)
            with jax.profiler.TraceAnnotation("cold_start/first_solve"), \
                    ph.phase("first_solve"):
                first = loadgen.Sent(-1, 0, b0, t0, t0)
                if self.served:
                    from repro.serve import SolveEngine, SolveFrontend
                    self.engine = SolveEngine(
                        self.cache, slots=int(cfg["slots"]),
                        iters_per_tick=int(cfg["iters_per_tick"]))
                    self.fe = SolveFrontend(self.engine, max_queue=1024)
                    fut = self._submit(b0)
                    try:
                        self._collect_served(first,
                                             fut.result(timeout=self.grace_s))
                    except FutureTimeout:
                        self.log(f"cold start: no answer in {self.grace_s} s")
                else:
                    self._collect_direct(first, self._solve(b0))
            first.t_done = time.perf_counter()
        if tracer:
            tracer.stop()
        return cc, first, first.t_done - t0, tracer

    def warm_up(self) -> None:
        """Compile or load the programs the window uses and no others: on
        the served path, the retirement gathers of 8, 4 and 2 lanes (the
        first solve brought the rest)."""
        if not self.served:
            return
        from repro.serve.engine import make_request
        engine, n = self.engine, self.graph.n

        def warm():
            # zero right-hand sides freeze at admission, so each group
            # retires in one tick through a gather of its own width
            rid = 10 ** 9
            for width in (8, 4, 2):
                reqs = []
                for _ in range(min(width, engine.slots)):
                    rid += 1
                    reqs.append(make_request(
                        self.cell.config_name, np.zeros(n, np.float32),
                        rid=rid, tol=self.tol, maxiter=self.maxiter))
                    engine.submit(reqs[-1])
                for _ in range(8):
                    if all(r.status for r in reqs):
                        break
                    engine.tick()

        with CompileCount() as cw:
            self.fe.call(warm).result()
        self.log(f"warm-up: programs compiled {cw.compiled}, loaded "
                 f"{cw.loaded}")

    # -- the measured window ----------------------------------------------
    def _segment(self, run: Run, seconds: float, trace: bool
                 ) -> loadgen.Segment:
        """The traced part of a window: its start and stop fall between
        engine ticks (served) or between solves (direct)."""
        if not trace:
            return loadgen.Segment()
        tracer, seg = _Tracer(), run.segment
        run.segment["tracer"] = tracer

        def start():
            tracer.start()
            _mark("segment/start")
            seg["tick0"] = self.engine.ticks if self.served else None
            seg["seq0"] = len(run.sent)

        def stop():
            seg["tick1"] = self.engine.ticks if self.served else None
            seg["seq1"] = len(run.sent)
            _mark("segment/stop")
            tracer.stop()

        if not self.served:
            return loadgen.Segment(SEGMENT_AT * seconds, SEGMENT_S, start,
                                   stop)

        def between_ticks(action):
            # hold the engine's driver thread between two ticks and act
            # from this thread meanwhile
            held, resume = threading.Event(), threading.Event()

            def hold():
                held.set()
                resume.wait(timeout=self.grace_s)

            fut = self.fe.call(hold)
            held.wait(timeout=self.grace_s)
            try:
                action()
            finally:
                resume.set()
            fut.result(timeout=self.grace_s)

        return loadgen.Segment(SEGMENT_AT * seconds, SEGMENT_S,
                               lambda: between_ticks(start),
                               lambda: between_ticks(stop))

    def window(self, run: Run, seconds: float, trace: bool = False) -> None:
        """Drive the cell's traffic from ``run.seed`` for ``seconds`` and
        keep what came back in ``run``."""
        segment = self._segment(run, seconds, trace)
        mix, n = self.cell.mix, self.graph.n
        with CompileCount() as cw, HostWatch() as hw:
            run.window_t0 = time.perf_counter()
            if self.served:
                run.sent = loadgen.drive_served(
                    mix, run.seed, n, seconds, self._submit,
                    self._collect_served, grace_s=self.grace_s,
                    segment=segment)
            else:
                # the segment counts solves through run.sent as they land
                loadgen.drive_direct(
                    mix, run.seed, n, seconds, self._solve,
                    lambda rec, res: (self._collect_direct(rec, res),
                                      run.sent.append(rec)),
                    segment=segment)
        run.window_compiles = cw
        if "tracer" in run.segment:
            t0 = time.perf_counter()
            run.window_trace = tr = run.segment.pop("tracer").load()
            self.log(f"window trace: {len(tr.ops)} device ops, "
                     f"{len(tr.modules)} program runs, read in "
                     f"{time.perf_counter() - t0!r} s")
        self.log(f"window: {len(run.sent)} requests sent, "
                 f"{len(run.done)} answered; programs compiled inside the "
                 f"window {cw.compiled} (loaded {cw.loaded}); "
                 + hw.report())

    def new_run(self, seed: int) -> Run:
        return Run(cell=self.cell, seed=seed, graph=self.graph,
                   device_kind=self.devs[0].device_kind,
                   cold_start_s=self.cold_start_s,
                   cold_compiles=self.cold_compiles, first=self.first,
                   nnz=self.nnz, sweeps_per_apply=self.sweeps_per_apply,
                   cold_trace=self.cold_trace)

    def close(self) -> None:
        """Stop the frontend's driver thread and free the program's
        state."""
        if self.fe is not None:
            self.fe.close(drain=False)
        self.fe = self.engine = self.h = self.cache = None
        gc.collect()


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             grace_s: float = 60.0, exit_if_compiled: bool = False,
             log=print) -> Run:
    """Set up, run and check one cell once.  Raises
    :class:`NoAccelerator` before any work when the chips are missing,
    and :class:`ColdStartCompiled` after the cold start where it
    compiled and ``exit_if_compiled`` is set."""
    s = Session(cell, seed, trace=trace, require_tpu=require_tpu,
                grace_s=grace_s, exit_if_compiled=exit_if_compiled,
                log=log)
    try:
        s.warm_up()
        run = s.new_run(seed)
        run.setup_s = time.perf_counter() - t_start
        s.window(run, seconds, trace)
        stats = s.devs[0].memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            run.memory_peak_bytes = int(stats["peak_bytes_in_use"])
    finally:
        s.close()
    return run


def check(run: Run) -> Dict[str, Dict]:
    """Every answer the window returned, and the cold start's first,
    against the float64 reference: the numbers compared, each with its
    limit.  Sets ``run.failed`` to the answers that miss any of them."""
    limit = float(run.cell.config["check"]["host_relres_max"])
    tol = float(run.cell.config["tol"])
    missing = unconverged = failed = 0
    worst = 0.0
    for r in [run.first] + run.sent:
        if r.x is None:
            missing += 1
            failed += 1
            continue
        bad = r.status != "converged" or not np.all(
            np.asarray(r.relres) <= tol)
        unconverged += bad
        res = [host_relres(run.graph, x, b)
               for x, b in zip(np.atleast_2d(r.x), np.atleast_2d(r.b))]
        res = [v if np.isfinite(v) else float("inf") for v in res]
        worst = max(worst, *res)
        failed += bad or max(res) > limit
    run.failed = failed
    return {
        "answers_missing": {"value": missing, "limit": 0},
        "answers_not_converged": {"value": unconverged, "limit": 0},
        "host_relres_max": {"value": worst, "limit": limit},
    }


def passed(compared: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def read_metrics(run: Run, metrics: List[Dict]) -> Dict[str, Dict]:
    out = {}
    for m in metrics:
        reader = _load_module(HERE / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def segment_bounds(tr: Optional[trace_reduce.Trace]):
    """The traced part of the window, in trace nanoseconds: from the end
    of the ``segment/start`` mark to the start of ``segment/stop``."""
    if tr is None:
        return None
    a = trace_reduce.span_named(tr, "segment/start")
    b = trace_reduce.span_named(tr, "segment/stop")
    if a is None or b is None or b[0] <= a[1]:
        return None
    return a[1], b[0]


def segment_work(run: Run) -> Optional[Dict]:
    """PCG passes, lane-iterations, step calls, least bytes and device
    busy seconds of the traced part of the window; None where there is
    nothing to read.  Computed once per run."""
    if "work" not in run.segment:
        run.segment["work"] = _segment_work(run)
    return run.segment["work"]


def _segment_work(run: Run) -> Optional[Dict]:
    bounds = segment_bounds(run.window_trace)
    if bounds is None or not run.window_trace.devices:
        return None
    seg = run.segment
    if run.cell.mix["path"] == "served":
        per = lanes.tick_advances(run.sent, int(run.cell.config[
            "iters_per_tick"]))
        if per is None:
            return None
        counts = lanes.segment_counts(per, seg["tick0"], seg["tick1"])
    else:
        recs = run.sent[seg["seq0"]:seg["seq1"]]
        its = [np.atleast_1d(r.iters) for r in recs if r.iters is not None]
        counts = {"passes": int(sum(i.max() for i in its)),
                  "advances": int(sum(i.sum() for i in its)),
                  "steps": len(its)}
    if counts["passes"] == 0:
        return None
    lo, hi = bounds
    counts["busy_s"] = trace_reduce.busy_ns(run.window_trace, lo, hi) * 1e-9
    counts["span_s"] = (hi - lo) * 1e-9
    counts["bytes"] = (counts["passes"] * work.factor_bytes(*run.factor_work)
                       + counts["advances"] * work.lane_bytes(run.graph.n))
    return counts

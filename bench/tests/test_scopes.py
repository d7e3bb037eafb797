"""The readers of the program's spans and kernel scopes, on a small
trace recorded on the CPU (``record_cpu_spans.py``).  On the CPU, XLA's
ops run on host threads and each op event carries its module and
program run in its stats; a TPU puts them on its device plane, with the
program runs on an ``XLA Modules`` line.  The fixture makes from the
CPU trace the lists ``trace_reduce.load`` makes from a TPU's."""
import gzip
import json
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, scopes, trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"
PASSES = 10


def _trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(gzip.decompress(
        (DATA / "cpu_spans.xplane.pb.gz").read_bytes()))
    t, runs = tr.Trace(), defaultdict(list)
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
                if "hlo_op" in stats:
                    t.ops.append((ev.name, s, e))
                    runs[stats["hlo_module"], stats["program_id"],
                         stats["run_id"]].append((s, e))
                elif "/" in ev.name and e > s and not ev.name.startswith("$"):
                    t.spans.append((ev.name, s, e))
    t.modules = [(f"{m}({p})", min(s for s, _ in iv), max(e for _, e in iv))
                 for (m, p, _), iv in runs.items()]
    t.devices = 1
    return t


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads(gzip.decompress(
        (DATA / "cpu_spans.json.gz").read_bytes()))
    tables = {tuple(k.split("|", 1)): {i: tuple(v) for i, v in t.items()}
              for k, t in meta["tables"].items()}
    return _trace(), meta, tables


@pytest.fixture
def run(recorded, monkeypatch):
    trace, meta, tables = recorded
    monkeypatch.setattr(scopes, "program_tables", lambda: tables)
    return SimpleNamespace(
        window_trace=trace, cold_trace=trace,
        segment={"tick0": meta["tick0"], "tick1": meta["tick1"],
                 "work": {"passes": PASSES}})


def read(name, run):
    return harness._load_module(
        harness.HERE / "metrics" / f"{name}.py").read(run)


def test_every_op_of_the_noted_programs_is_matched(run):
    """Each op of the engine's programs is put down to one scope (or to
    none); only the eager one-op programs the program never notes stay
    unmatched.  On the CPU the compiler's loop-carry copies, which
    carry no op_name, leave a tenth of the step's op time unscoped."""
    got = scopes.by_scope(run)
    engine = ("jit_admit/", "jit_step/", "jit_gather/")
    assert all(sc != scopes.UNMATCHED for op, sc in got["ops"].items()
               if op.startswith(engine))
    assert {"trisolve_fleet", "fleet_matvec", "pcg_update"} \
        <= set(got["intervals"])
    assert 0.8 < got["covered"] <= 1.0


def test_per_pass_readers(run):
    lo, hi = harness.segment_bounds(run.window_trace)
    busy = tr.busy_ns(run.window_trace, lo, hi) * 1e-9
    tri, mv = (scopes.seconds(run, s)
               for s in ("trisolve_fleet", "fleet_matvec"))
    assert 0 < tri and 0 < mv and tri + mv <= busy
    assert read("trisolve_ms_per_pass", run) == 1e3 * tri / PASSES
    assert read("matvec_ms_per_pass", run) == 1e3 * mv / PASSES


def test_disagreeing_tables_leave_ops_unmatched(recorded, run,
                                                monkeypatch):
    """Two tables of one module that fit the trace's ops but name their
    scopes differently put those ops down to neither."""
    _, _, tables = recorded
    (mod, fp), step = next((k, t) for k, t in tables.items()
                           if k[0] == "jit_step")
    other = {i: (sig, "pcg_update") for i, (sig, _) in step.items()}
    monkeypatch.setattr(scopes, "program_tables",
                        lambda: {**tables, (mod, "other"): other})
    got = scopes.by_scope(run)
    assert scopes.UNMATCHED in got["intervals"]
    assert not any(op.startswith("jit_step/") and sc != scopes.UNMATCHED
                   and sc != "pcg_update" for op, sc in got["ops"].items())


def test_a_table_of_other_shapes_does_not_fit(monkeypatch):
    """Where the trace prints an op's instruction (a TPU), a table of the
    module whose instruction of that name has another shape or opcode
    is not that program's."""
    op = "%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8]{0} %p), k=1"
    trace = tr.Trace(ops=[(op, 10.0, 20.0)],
                     modules=[("jit_run(77)", 5.0, 25.0)],
                     spans=[("segment/start", 0.0, 1.0),
                            ("segment/stop", 30.0, 31.0)], devices=1)
    tables = {("jit_run", "a"): {"fusion.3": ("f32[8,128] fusion",
                                              "trisolve_fleet")},
              ("jit_run", "b"): {"fusion.3": ("f32[3] fusion",
                                              "pcg_update")},
              ("jit_step", "c"): {"fusion.3": ("f32[8,128] fusion",
                                               "fleet_matvec")}}
    monkeypatch.setattr(scopes, "program_tables", lambda: tables)
    run = SimpleNamespace(window_trace=trace, segment={})
    assert scopes.by_scope(run)["ops"] == {"jit_run/%fusion.3":
                                           "trisolve_fleet"}
    assert scopes.seconds(run, "trisolve_fleet") == 10e-9


def test_engine_idle_ms_per_tick(run):
    tr_ = run.window_trace
    bounds = harness.segment_bounds(tr_)
    ticks = run.segment["tick1"] - run.segment["tick0"]
    idle = sum(e - s for s, e in tr.gaps(tr_, *bounds))
    v = read("engine_idle_ms_per_tick", run)
    assert ticks > 0 and 0 < v <= 1e-6 * idle / ticks


def test_construct_eliminate_s(run):
    fac = tr.span_named(run.cold_trace, "cold_start/factor")
    elim = [e - s for n, s, e in run.cold_trace.spans
            if n == "construct/eliminate"]
    v = read("construct_eliminate_s", run)
    assert len(elim) == 1 and v == elim[0] * 1e-9
    assert 0 < v < (fac[1] - fac[0]) * 1e-9


def test_readers_read_nothing_from_a_program_without_spans(run,
                                                          monkeypatch):
    """A program older than its spans and scope tables: every new
    reader returns None, and none raises."""
    monkeypatch.setattr(scopes, "program_tables", lambda: None)
    keep = ("segment/", "cold_start/")
    bare = tr.Trace(ops=run.window_trace.ops,
                    modules=run.window_trace.modules,
                    spans=[s for s in run.window_trace.spans
                           if s[0].startswith(keep)], devices=1)
    old = SimpleNamespace(window_trace=bare, cold_trace=bare,
                          segment=dict(run.segment))
    for name in ("trisolve_ms_per_pass", "matvec_ms_per_pass",
                 "engine_idle_ms_per_tick", "construct_eliminate_s"):
        assert read(name, old) is None, name

"""In-program spans and kernel scopes (``repro.obs.tracing``): the
spans a profiler trace of the serving and construction paths holds, the
kernel scopes compiled into the solve programs, the scope tables read
from the executables, and the sweep count a handle keeps."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import FactorCache
from repro.data import graphs
from repro.obs import tracing
from repro.serve import SolveEngine, SolveFrontend

NAMESPACES = ("engine/", "frontend/", "solver/", "fleet/", "construct/")
TICK_PHASES = ("engine/admit", "engine/step", "engine/readback",
               "engine/retire")
CONSTRUCTION = ("construct/pool", "construct/eliminate",
                "construct/finalize", "construct/schedules",
                "construct/pack", "construct/admit")


def _rhs(n, seed):
    b = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    return b - b.mean()


def _serve(cache, b):
    """One request through a frontend and engine, and one control call;
    returns the answer's iterate."""
    engine = SolveEngine(cache, slots=2, iters_per_tick=8)
    with SolveFrontend(engine) as fe:
        req = fe.submit("g", b, tol=1e-6, maxiter=200).result(timeout=120)
        fe.call(lambda: None).result(timeout=60)
    return np.asarray(req.x)


def _start(d):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(d), profiler_options=opts)


def _program_spans(d):
    """``[(line, name, start, end)]`` of the program's spans in the
    trace written under ``d``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(d), "plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(NAMESPACES):
                    out.append(((plane.name, li), ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Construction, a direct solve and a served request, once with no
    profiler session and once inside one.  Inside it the fleet also
    holds a second graph of the bucket, admitted before the session, so
    the engine's two lanes step on the per-lane index path (and the
    one-column direct solve on the shared one)."""
    g = graphs.grid2d(12, 12, seed=3)
    b = _rhs(g.n, 0)
    plain = FactorCache(fill_slack=64)
    h = plain.factor(g, jax.random.key(0), graph_id="g")
    off = (jax.device_get(h.solve(b, tol=1e-6, maxiter=200)),
           _serve(plain, b))
    cache = FactorCache(fill_slack=64)
    cache.factor(graphs.grid2d(12, 12, seed=8), jax.random.key(1),
                 graph_id="other")
    d = tmp_path_factory.mktemp("trace")
    _start(d)
    try:
        h_on = cache.factor(g, jax.random.key(0), graph_id="g")
        on = (jax.device_get(h_on.solve(b, tol=1e-6, maxiter=200)),
              _serve(cache, b))
    finally:
        jax.profiler.stop_trace()
    return {"spans": _program_spans(d), "off": off, "on": on,
            "handle": h_on}


def test_span_names_are_readable_by_the_trace_reduction():
    """Each name has a ``/``, starts with no ``$`` and ends in no
    ``/await`` (the rules a trace reduction keeps a span and names a
    gap by), and none is one of the benchmark's own namespaces."""
    names = tracing.PROGRAM_SPANS
    assert len(set(names)) == len(names)
    for n in names:
        assert "/" in n and not n.startswith("$") \
            and not n.endswith("/await")
        assert not n.startswith(("cold_start/", "client/", "segment/"))


def test_trace_holds_every_program_span(traced):
    assert traced["handle"].fleet.capacity == 2
    names = {n for _, n, _, _ in traced["spans"]}
    assert names <= set(tracing.PROGRAM_SPANS)
    assert names == set(tracing.PROGRAM_SPANS)


def test_spans_nest_as_listed(traced):
    spans = traced["spans"]
    ticks = [(ln, s, e) for ln, n, s, e in spans if n == "engine/tick"]
    for ln, n, s, e in spans:
        if n in TICK_PHASES:
            assert any(ln == tl and ts <= s and e <= te
                       for tl, ts, te in ticks), n
    # construction's stages follow one another, in order, apart
    stages = sorted((s, e, n) for _, n, s, e in spans
                    if n.startswith("construct/"))
    assert [n for _, _, n in stages] == list(CONSTRUCTION)
    assert all(a[1] <= b[0] for a, b in zip(stages, stages[1:]))
    solve, = [(s, e) for _, n, s, e in spans if n == "solver/solve"]
    assert stages[-1][1] <= solve[0]
    # each fleet program call lies inside a step's or a solve's dispatch
    calls = [(ln, s, e) for ln, n, s, e in spans
             if n in ("engine/step", "solver/solve")]
    for ln, n, s, e in spans:
        if n.startswith("fleet/"):
            assert any(ln == cl and cs <= s and e <= ce
                       for cl, cs, ce in calls), n


def test_answers_bit_identical_with_profiler_on_and_off(traced):
    (res_off, x_off), (res_on, x_on) = traced["off"], traced["on"]
    for a, b in zip(res_off, res_on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(x_off, x_on)
    assert bool(res_on.converged)


def test_compiled_programs_carry_kernel_scopes(traced):
    """The step and the direct solve, as compiled, name the trisolve,
    the matvec and the vector work in their instructions' op_name."""
    tables = tracing.scope_tables()
    want = {"trisolve_fleet", "fleet_matvec", "pcg_update"}
    for module in ("jit_run", "jit_step"):
        got = [t for (m, _), t in tables.items() if m == module]
        assert got, module
        scopes = {sc for t in got for _, sc in t.values()}
        assert want <= scopes, (module, scopes)


def test_scope_table_costs_no_backend_compile(traced, tmp_path):
    import jax.monitoring as mon
    h = traced["handle"]
    # a block of two right-hand sides: a program not yet tabled
    B = jnp.asarray(np.stack([_rhs(h.n, 1), _rhs(h.n, 2)]))
    _start(tmp_path)
    try:
        h.solve(B, tol=1e-6, maxiter=200).x.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    compiles = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    mon.register_event_duration_secs_listener(on_duration)
    try:
        tables = tracing.scope_tables()
    finally:
        mon.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert len([m for m, _ in tables if m == "jit_run"]) == 2


def test_nothing_noted_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = len(tracing._noted)
    tracing.note_program(jax.jit(jnp.sin), jnp.ones(3))
    assert len(tracing._noted) == before


@pytest.mark.parametrize("text, want", [
    ("%fusion.47 = f32[8,65536]{1,0:T(8,128)S(1)} fusion(f32[8]{0} %p), "
     "kind=kLoop", ("fusion.47", "f32[8,65536] fusion")),
    ("ROOT %while.5 = (s32[]{:T(128)}, f32[4]{0}) while((s32[], f32[4]) "
     "%t), condition=%c", ("while.5", "(s32[], f32[4]) while")),
    ("broadcast_add_fusion", ("broadcast_add_fusion", "")),
])
def test_instruction_signature(text, want):
    assert tracing.instruction_signature(text) == want


def test_scope_of_takes_the_outermost_kernel_scope():
    path = ("jit(step)/while/body/trisolve_fleet/while/body/"
            "jit(ell_spmv_fleet)/ell_spmv_fleet/mul")
    assert tracing.scope_of(path) == "trisolve_fleet"
    assert tracing.scope_of("jit(run)/pcg_update/add") == "pcg_update"
    assert tracing.scope_of("jit(run)/while/body/add") is None


def test_parse_scope_table():
    text = ('HloModule jit_run, is_scheduled=true\n\n'
            '%fused (p: f32[4]) -> f32[4] {\n'
            '  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name='
            '"jit(run)/fleet_matvec/mul"}\n}\n\n'
            'ENTRY %main {\n'
            '  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused, '
            'metadata={op_name="jit(run)/fleet_matvec/mul" '
            'stack_frame_id=2}\n'
            '  ROOT %add.2 = f32[4]{0} add(%fusion.1, %x), '
            'metadata={op_name="jit(run)/while/add"}\n}\n')
    module, table = tracing.parse_scope_table(text)
    assert module == "jit_run"
    assert table["fusion.1"] == ("f32[4] fusion", "fleet_matvec")
    assert table["add.2"] == ("f32[4] add", None)


def test_scope_table_fills_scopes_the_compiler_dropped():
    """A loop body a scoped loop calls runs under its scope, whatever
    its instructions' own op_name; a fusion with no op_name takes the
    one scope of what it fuses; a loop that no scope holds passes none
    on."""
    text = (
        'HloModule jit_step, is_scheduled=true\n\n'
        '%body.1 (p: f32[4]) -> f32[4] {\n'
        '  %g.1 = f32[4]{0} gather(%p, %p), metadata={op_name="gather"}\n'
        '  ROOT %c.1 = f32[4]{0} copy(%g.1)\n}\n\n'
        '%fused.2 (p: f32[4]) -> f32[4] {\n'
        '  ROOT %s.2 = f32[4]{0} scatter(%p, %p, %p), metadata={op_name='
        '"jit(step)/while/body/fleet_matvec/scatter-add"}\n}\n\n'
        '%body.3 (p: f32[4]) -> f32[4] {\n'
        '  %w.3 = f32[4]{0} while(%p), condition=%cond.4, body=%body.1, '
        'metadata={op_name="jit(step)/while/body/trisolve_fleet/while"}\n'
        '  %f.3 = f32[4]{0} fusion(%w.3), kind=kCustom, calls=%fused.2\n'
        '  ROOT %k.3 = f32[4]{0} copy(%f.3)\n}\n\n'
        'ENTRY %main (x: f32[4]) -> f32[4] {\n'
        '  ROOT %w.5 = f32[4]{0} while(%x), condition=%cond.6, '
        'body=%body.3, metadata={op_name="jit(step)/while"}\n}\n')
    _, table = tracing.parse_scope_table(text)
    assert {i: sc for i, (_, sc) in table.items()} == {
        "g.1": "trisolve_fleet", "c.1": "trisolve_fleet",
        "s.2": "fleet_matvec", "w.3": "trisolve_fleet",
        "f.3": "fleet_matvec", "k.3": None, "w.5": None}


@pytest.mark.parametrize("graph", ["grid2d", "contrast3d", "powerlaw"])
def test_sweeps_per_apply_counts_the_sweeps_run(monkeypatch, graph):
    """The handle's count equals the sweeps ``trisolve_fleet`` runs in
    one forward plus one backward apply of one lane (each sweep makes
    one ``ell_spmv_fleet`` call), on factors of one panel class and of
    several."""
    from repro.kernels import ops
    g = {"grid2d": lambda: graphs.grid2d(12, 12, seed=3),
         "contrast3d": lambda: graphs.grid3d(8, 8, 8, "contrast", seed=13),
         "powerlaw": lambda: graphs.powerlaw(300, 4, seed=3)}[graph]()
    h = FactorCache(fill_slack=64).factor(g, jax.random.key(0),
                                          graph_id="g")
    assert h._sweeps is None                  # nothing walked at admission
    calls, spmv = [0], ops.ell_spmv_fleet

    def counting(*a, **k):
        calls[0] += 1
        return spmv(*a, **k)

    monkeypatch.setattr(ops, "ell_spmv_fleet", counting)
    with jax.disable_jit():
        h.precondition(jnp.asarray(_rhs(g.n, 2)))
    assert calls[0] > 0
    assert h.sweeps_per_apply == calls[0]

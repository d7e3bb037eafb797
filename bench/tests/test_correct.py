"""What decides ``correct``: the control fails the limit, and a run
with its timed path broken comes out not correct — once for each fault
a cell can have.  On the CPU, at small sizes."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import DirectSolve, host_relres, round_to


CONFIGS = [
    ("grid2d_256", "grid2d", {"nx": 64, "ny": 64, "seed": 11}, 4096, 8064),
    ("contrast3d_32", "grid3d", {"nx": 16, "ny": 16, "nz": 16,
                                 "kind": "contrast", "seed": 13},
     4096, 11520)]


def _direct_run(cell_of, config, gen, params, n, m, seed, *, tol=None):
    """A sound direct run of ``config`` cut to ``n`` vertices; with
    ``tol`` the program solves to that tolerance instead of the
    configuration's."""
    cell = cell_of("direct", config, generator=gen, params=params, n=n,
                   m=m)
    session = harness.Session(cell, seed, require_tpu=False,
                              log=lambda _: None)
    try:
        if tol is not None:
            session.tol = tol
        run = session.new_run(seed)
        session.window(run, 1.0)
    finally:
        session.close()
    return run


@pytest.mark.parametrize("config,gen,params,n,m", CONFIGS)
def test_control_fails_the_limit(cell_of, config, gen, params, n, m):
    """The program's float32 answers pass the check; the control in
    their place — the exact answer of every request, stored in bfloat16
    — fails it, on three seeds."""
    for seed in (2 ** 31 + 17, 2 ** 31 + 18, 2 ** 31 + 19):
        run = _direct_run(cell_of, config, gen, params, n, m, seed)
        assert len(run.sent) >= 2
        assert harness.passed(harness.check(run))
        ref = DirectSolve(run.graph)
        for r in [run.first] + run.sent:
            x = ref.solve(r.b)
            assert host_relres(run.graph, x, r.b) < 1e-9
            r.x = round_to(x, "bfloat16")
            r.status, r.relres = "converged", np.zeros(1)
        compared = harness.check(run)
        assert compared["answers_not_converged"]["value"] == 0
        assert not harness.passed(compared), compared
        assert run.failed == len(run.sent) + 1


@pytest.mark.parametrize("config,gen,params,n,m", CONFIGS)
def test_early_stop_fails_the_limit(cell_of, config, gen, params, n, m):
    """A program that stops at tol 1e-3 instead of 1e-6 fails the
    independent residual limit, not only its own converged flag."""
    run = _direct_run(cell_of, config, gen, params, n, m, 2 ** 31 + 23,
                      tol=1e-3)
    compared = harness.check(run)
    assert compared["host_relres_max"]["value"] \
        > compared["host_relres_max"]["limit"], compared


def _keep_half(new, old):
    half = new.X.shape[0] // 2
    keep = jnp.arange(new.X.shape[0]) < half

    def pick(a, b):
        m = keep.reshape((-1,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(pick, new, old)


def _faults(monkeypatch, path, fault):
    import repro.core.solver as solver
    import repro.serve.engine as engine
    from repro.core import pcg
    if path == "served" and fault == "state_unchanged":
        monkeypatch.setattr(engine, "_step_program",
                            lambda fa, state, **kw: state)
    elif path == "served" and fault == "half_batch":
        step = engine._step_program
        monkeypatch.setattr(engine, "_step_program",
                            lambda fa, state, **kw: _keep_half(
                                step(fa, state, **kw), state))
    elif path == "served" and fault == "answer_altered":
        gather = engine._gather_program
        monkeypatch.setattr(
            engine, "_gather_program", lambda state, rows: (
                lambda X, it, rr: (X.at[:, 0].add(1.0), it, rr))(
                    *gather(state, rows)))
    elif path == "direct" and fault == "state_unchanged":
        monkeypatch.setattr(solver, "pcg_fleet_solve",
                            lambda fa, fidx, B, tol, maxiter, **kw:
                            pcg.pcg_fleet_init(fa, fidx, B, tol, maxiter,
                                               **kw))
    elif path == "direct" and fault == "answer_altered":
        result = solver.pcg_fleet_result
        monkeypatch.setattr(
            solver, "pcg_fleet_result", lambda state, n: (
                lambda r: r._replace(x=r.x.at[:, 0].add(1.0)))(
                    result(state, n)))
    else:
        raise AssertionError((path, fault))


@pytest.mark.parametrize("traffic,fault", [
    ("lanes8", "state_unchanged"), ("lanes8", "half_batch"),
    ("lanes8", "answer_altered"), ("direct", "state_unchanged"),
    ("direct", "answer_altered")])
def test_broken_timed_path_is_not_correct(cell_of, monkeypatch, traffic,
                                          fault):
    cell = cell_of(traffic)
    _faults(monkeypatch, cell.mix["path"], fault)
    run = harness.run_cell(cell, 2 ** 31 + 5, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           grace_s=3.0, log=lambda _: None)
    compared = harness.check(run)
    assert not harness.passed(compared), compared
    assert run.failed > 0


def test_sound_run_is_correct(cell_of):
    cell = cell_of("lanes8")
    run = harness.run_cell(cell, 2 ** 31 + 5, 2.0, False,
                           t_start=time.perf_counter(), require_tpu=False,
                           log=lambda _: None)
    compared = harness.check(run)
    assert harness.passed(compared), compared
    assert run.failed == 0 and len(run.sent) >= 8
    assert np.all([r.status == "converged" for r in run.sent])

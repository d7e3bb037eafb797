"""The work model: bytes from true sizes, sweeps from the plan, and the
peaks table."""
import jax
import numpy as np
import pytest

from bench import harness, work


def test_peaks_table():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v4")


def test_iteration_bytes():
    # a factor of 10 entries on a 4-vertex, 3-edge graph, two lanes
    assert work.factor_bytes(10, 4, 3) == 10 * 8 + 4 * 4 + 3 * 12
    assert work.iteration_bytes([(10, 4, 3)], [4, 4]) \
        == 10 * 8 + 4 * 4 + 3 * 12 + 2 * 8 * 4 * 4


def test_trisolve_sweeps_walks_runs():
    # 8-slot panels (one class), 6 rows: levels {0: 1 row, 1: 3, 2: 2},
    # two rows per sweep -> level 1 takes 2 sweeps, level 2 one
    extent = np.array([0, 3, 2, 1, 4, 4])
    group_end = np.array([1, 4, 4, 4, 6, 6])
    level_ptr = np.array([0, 1, 4, 6])
    assert work.trisolve_sweeps(extent, group_end, level_ptr, 3, 8, 2) == 3
    # a lane that stops after level 1 does level 1 only
    assert work.trisolve_sweeps(extent, group_end, level_ptr, 2, 8, 2) == 2
    # one level: nothing to sweep
    assert work.trisolve_sweeps(extent, group_end, level_ptr, 1, 8, 2) == 0


def _handle(cache, g, gid):
    return cache.factor(g, jax.random.key(0), graph_id=gid, family="ac")


def test_bytes_ignore_padding():
    """The same graph reads the same bytes whatever its fleet pads it
    to: alone in a tight fleet, or beside a hub-heavy graph that widens
    the fleet's panels, and whatever the lane count."""
    from repro.core.solver import FactorCache
    from repro.data import graphs
    g = graphs.grid2d(12, 12, seed=3)
    hub = graphs.powerlaw(200, 8, seed=3)
    tight = FactorCache(fill_slack=64)
    h1 = _handle(tight, g, "g")
    wide = FactorCache(fill_slack=64, k_tiering=False)
    _handle(wide, hub, "hub")
    h2 = _handle(wide, g, "g")
    assert h1.n_pad == h2.n_pad and h2.fleet.Kf > h1.fleet.Kf
    for slots in (1, 8):
        b1 = work.iteration_bytes([(h1.factor.nnz, g.n, g.m)], [g.n] * slots)
        b2 = work.iteration_bytes([(h2.factor.nnz, g.n, g.m)], [g.n] * slots)
        assert b1 == b2
    # and the sweep count of the one factor is read from its own plan
    assert harness._sweeps_per_apply(h1) > 0


@pytest.mark.parametrize("graph", ["grid2d", "contrast3d", "powerlaw"])
def test_sweeps_per_apply_counts_the_sweeps_run(monkeypatch, graph):
    """The metric equals the sweeps the program's trisolve runs in one
    forward plus one backward apply of one lane (each sweep makes one
    ``ell_spmv_fleet`` call), on factors of one panel class and of
    several."""
    import jax.numpy as jnp
    from repro.core.solver import FactorCache
    from repro.data import graphs
    from repro.kernels import ops
    g = {"grid2d": lambda: graphs.grid2d(12, 12, seed=3),
         "contrast3d": lambda: graphs.grid3d(8, 8, 8, "contrast", seed=13),
         "powerlaw": lambda: graphs.powerlaw(300, 4, seed=3)}[graph]()
    h = _handle(FactorCache(fill_slack=64), g, "g")
    calls, spmv = [0], ops.ell_spmv_fleet

    def counting(*a, **k):
        calls[0] += 1
        return spmv(*a, **k)

    monkeypatch.setattr(ops, "ell_spmv_fleet", counting)
    r = np.random.default_rng(0).standard_normal(g.n).astype(np.float32)
    # eager: the sweep loop runs in Python and each sweep runs its own
    # panel class's branch alone
    with jax.disable_jit():
        h.precondition(jnp.asarray(r - r.mean()))
    assert calls[0] > 0
    assert harness._sweeps_per_apply(h) == calls[0]

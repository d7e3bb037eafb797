"""Device-resident factor→solve pipeline: compaction, device schedules,
batched multi-RHS PCG, and the ``Solver`` lifecycle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.laplacian import laplacian_matvec_np
from repro.core.ref_ac import factorize_sequential
from repro.core.parac import factorize_wavefront, _build_pool, _compact_pool
from repro.core.trisolve import (build_schedules, build_schedules_device,
                                 solve_levels_np, make_ell_solver,
                                 make_preconditioner)
from repro.core.pcg import laplacian_pcg_jax, laplacian_pcg_jax_batched
from repro.core.solver import Solver
from repro.kernels import ops as kops
from repro.data import graphs


KEY = jax.random.key(7)


@pytest.fixture(scope="module")
def g_small():
    return graphs.grid2d(12, 12, seed=3)


@pytest.fixture(scope="module")
def handle(g_small):
    return Solver(chunk=32, fill_slack=64).factor(g_small, KEY)


# ---------------------------------------------------------------------------
# Device compaction == old host loop
# ---------------------------------------------------------------------------

def _host_compact(pool_row, pool_val, col_fill, col_base, dtype):
    """The pre-refactor per-column host loop, kept as the oracle."""
    n = col_fill.shape[0]
    lens = col_fill.astype(np.int64)
    col_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=col_ptr[1:])
    rows = np.empty(col_ptr[-1], np.int32)
    vals = np.empty(col_ptr[-1], dtype)
    for k in range(n):
        b = col_base[k]
        rows[col_ptr[k]:col_ptr[k + 1]] = pool_row[b:b + col_fill[k]]
        vals[col_ptr[k]:col_ptr[k + 1]] = pool_val[b:b + col_fill[k]]
    return col_ptr, rows, vals


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_compaction_matches_host_loop(seed):
    rng = np.random.default_rng(seed)
    g = graphs.powerlaw(120 + 30 * seed, 4, seed=seed)
    pool_row, pool_val, fill, dep, col_base, cap, P, dmax = \
        _build_pool(g, 8, np.float32)
    # scramble fills to exercise ragged slabs (any fill <= cap is legal)
    fill = rng.integers(0, cap + 1).astype(np.int32)
    rows_c, vals_c, col_ptr_d = _compact_pool(
        jnp.asarray(pool_row), jnp.asarray(pool_val), jnp.asarray(fill),
        jnp.asarray(col_base))
    nnz = int(col_ptr_d[-1])
    ref_ptr, ref_rows, ref_vals = _host_compact(
        pool_row, pool_val, fill, col_base, np.float32)
    assert np.array_equal(np.asarray(col_ptr_d).astype(np.int64), ref_ptr)
    assert np.array_equal(np.asarray(rows_c)[:nnz], ref_rows)
    assert np.array_equal(np.asarray(vals_c)[:nnz], ref_vals)


def test_wavefront_factor_is_device_resident(g_small):
    f = factorize_wavefront(g_small, KEY, fill_slack=64)
    assert f.device is not None
    assert isinstance(f.device.rows, jax.Array)
    assert np.array_equal(np.asarray(f.device.rows), f.rows)
    assert np.array_equal(np.asarray(f.device.col_ptr), f.col_ptr)
    assert np.array_equal(np.asarray(f.device.vals), f.vals)


# ---------------------------------------------------------------------------
# Device level schedule == host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("maker", [
    lambda: graphs.grid2d(10, 11, seed=1),
    lambda: graphs.powerlaw(300, 5, seed=3),
    lambda: graphs.road_like(12, seed=4),
])
def test_device_levels_match_host_oracle(maker):
    g = maker()
    f = factorize_sequential(g, KEY)
    fwd_h, bwd_h = build_schedules(f)       # host _levels_from_edges path
    fwd_d, bwd_d = build_schedules_device(f)
    for h, d in ((fwd_h, fwd_d), (bwd_h, bwd_d)):
        assert d.n_levels == h.n_levels
        assert np.array_equal(np.asarray(d.level_of), h.level_of)
        # same rows per level (row_ids sorted by level, ties by index)
        lv_of_sorted = np.asarray(d.level_of)[np.asarray(d.row_ids)]
        assert np.all(np.diff(lv_of_sorted) >= 0)
        counts_d = np.diff(d.row_ptr)
        counts_h = np.bincount(h.level_of, minlength=h.n_levels)
        assert np.array_equal(counts_d, counts_h)


def test_ell_solver_matches_host_solve(g_small):
    f = factorize_sequential(g_small, KEY)
    fwd_h, bwd_h = build_schedules(f)
    fwd_d, bwd_d = build_schedules_device(f)
    b = np.random.default_rng(2).normal(size=f.n).astype(np.float32)
    yd = jax.jit(make_ell_solver(fwd_d))(jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(yd), solve_levels_np(fwd_h, b),
                               rtol=2e-4, atol=2e-4)
    xd = jax.jit(make_ell_solver(bwd_d, flip=True))(jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(xd),
                               solve_levels_np(bwd_h, b, flip=True),
                               rtol=2e-4, atol=2e-4)


def test_ell_solver_multi_rhs_matches_single(g_small):
    f = factorize_sequential(g_small, KEY)
    fwd_d, _ = build_schedules_device(f)
    solve = jax.jit(make_ell_solver(fwd_d))
    B = np.random.default_rng(3).normal(size=(f.n, 5)).astype(np.float32)
    YB = np.asarray(solve(jnp.asarray(B)))
    for j in range(5):
        yj = np.asarray(solve(jnp.asarray(B[:, j])))
        np.testing.assert_allclose(YB[:, j], yj, rtol=1e-6, atol=1e-7)


def test_masked_trisolve_matches_host(g_small):
    """The traced-argument level-masked trisolve (row-indexed packed
    panels, no closed-over slabs) matches the host oracle — including
    with an over-padded level bound (extra levels are masked no-ops)."""
    from repro.core.trisolve import build_schedules_batched
    f = factorize_sequential(g_small, KEY)
    fwd_h, bwd_h = build_schedules(f)
    (fwd_p, bwd_p), = build_schedules_batched([f.to_device()])
    b = np.random.default_rng(6).normal(size=f.n).astype(np.float32)
    bp = jnp.zeros(fwd_p.n_pad, jnp.float32).at[:f.n].set(jnp.asarray(b))
    y = kops.trisolve_masked(fwd_p.cols, fwd_p.vals, fwd_p.level_of, bp,
                             n_levels=fwd_p.n_levels)
    np.testing.assert_allclose(np.asarray(y)[:f.n],
                               solve_levels_np(fwd_h, b),
                               rtol=3e-4, atol=3e-4)
    y_over = kops.trisolve_masked(fwd_p.cols, fwd_p.vals, fwd_p.level_of,
                                  bp, n_levels=fwd_p.n_levels + 7)
    assert np.array_equal(np.asarray(y), np.asarray(y_over))
    # backward panels live in original index space: no flip needed
    x = kops.trisolve_masked(bwd_p.cols, bwd_p.vals, bwd_p.level_of, bp,
                             n_levels=bwd_p.n_levels)
    x_ref = solve_levels_np(bwd_h, b, flip=True)
    np.testing.assert_allclose(np.asarray(x)[:f.n], x_ref,
                               rtol=3e-4, atol=3e-4)


def test_fleet_trisolve_level_window_is_bit_exact(g_small):
    """The fleet trisolve's level window (each sweep gathers only the
    rows of its level, up to the widest level) changes the work, never
    a result: bit-identical to sweeping every row, and to the host
    oracle within float32 tolerance."""
    from repro.core.trisolve import build_schedules_batched
    f = factorize_sequential(g_small, KEY)
    fwd_h, bwd_h = build_schedules(f)
    (fwd_p, bwd_p), = build_schedules_batched([f.to_device()])
    B = np.random.default_rng(7).normal(size=(3, f.n)).astype(np.float32)
    Bp = jnp.zeros((3, fwd_p.n_pad), jnp.float32).at[:, :f.n].set(B)
    for p, flip, h in ((fwd_p, False, fwd_h), (bwd_p, True, bwd_h)):
        assert 1 <= p.level_width < f.n
        lanes = [jnp.stack([a] * 3) for a in (p.cols, p.vals, p.level_of)]
        full = kops.trisolve_fleet(*lanes, Bp, n_levels=p.n_levels)
        win = kops.trisolve_fleet(*lanes, Bp, n_levels=p.n_levels,
                                  width=p.level_width)
        assert np.array_equal(np.asarray(full), np.asarray(win))
        for j in range(3):
            np.testing.assert_allclose(
                np.asarray(win)[j, :f.n], solve_levels_np(h, B[j], flip=flip),
                rtol=3e-4, atol=3e-4)


def _random_panels(rng, n, K, n_levels):
    """A random unit-lower-triangular solve as row-indexed panels: row
    ``i``'s in-edges come from earlier rows of lower level, with in-
    degrees spread over 1..K so every panel class (8, 16, …, K) occurs.
    Returns cols, vals, level_of and the dense strictly-lower matrix."""
    level = np.sort(rng.integers(0, n_levels, n))
    level[0] = 0
    cols = np.zeros((n, K), np.int32)
    vals = np.zeros((n, K), np.float32)
    dense = np.zeros((n, n))
    for i in range(n):
        src = np.flatnonzero(level < level[i])
        if level[i] == 0 or src.size == 0:
            level[i] = 0
            continue
        d = int(min(src.size, rng.choice([1, 3, 8, 9, 17, K])))
        pick = rng.choice(src, d, replace=False)
        cols[i, :d] = pick
        vals[i, :d] = rng.normal(size=d) / d
        dense[i, pick] = vals[i, :d]
    # longest-path levels of the picked edges
    lv = np.zeros(n, np.int32)
    for i in range(n):
        src = cols[i, :np.count_nonzero(vals[i])]
        lv[i] = 1 + lv[src].max() if src.size else 0
    return cols, vals, lv, dense


def test_fleet_trisolve_panel_classes_are_bit_exact():
    """Rows of wide and narrow panel classes in one solve: the sweep
    width, the plan's source (in-program or host-built) and the other
    lanes of a batch change the work, never a lane's result, and every
    lane matches a dense float64 solve."""
    from repro.core.trisolve import sweep_plan_np
    rng = np.random.default_rng(3)
    n, K = 96, 32
    lanes = [_random_panels(rng, n, K, 9) for _ in range(2)]
    y0 = rng.normal(size=(2, n)).astype(np.float32)
    cols = jnp.asarray(np.stack([c for c, _, _, _ in lanes]))
    vals = jnp.asarray(np.stack([v for _, v, _, _ in lanes]))
    lvl = jnp.asarray(np.stack([l for _, _, l, _ in lanes]))
    nl = int(lvl.max()) + 1
    ext = (np.asarray(vals) != 0).sum(axis=2)
    host = [sweep_plan_np(np.asarray(lvl[j]), ext[j], nl) for j in range(2)]
    host_plan = tuple(jnp.asarray(np.stack(a)) for a in zip(*host))
    dev_plan = kops.sweep_plan(vals, lvl, nl)
    for a, b in zip(host_plan, dev_plan):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    ref = kops.trisolve_fleet(cols, vals, lvl, jnp.asarray(y0), n_levels=nl)
    # with a plan, panels come in plan order (as a fleet stores them)
    by_plan = [jnp.take_along_axis(a, host_plan[0][:, :, None], axis=1)
               for a in (cols, vals)]
    for width in (1, 5, 16):
        got = kops.trisolve_fleet(*by_plan, lvl, jnp.asarray(y0),
                                  n_levels=nl, width=width, plan=host_plan)
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    # lane 0 alone, and beside a copy of itself, as in the pair above
    for pick in ([0], [0, 0]):
        ix = jnp.asarray(pick)
        got = kops.trisolve_fleet(cols[ix], vals[ix], lvl[ix],
                                  jnp.asarray(y0)[ix], n_levels=nl, width=5)
        assert np.array_equal(np.asarray(got)[0], np.asarray(ref)[0])
    for j, (_, _, _, dense) in enumerate(lanes):
        want = np.linalg.solve(np.eye(n) + dense, y0[j].astype(np.float64))
        np.testing.assert_allclose(np.asarray(ref)[j], want, rtol=1e-4,
                                   atol=1e-4)


def test_pallas_panel_trisolve_matches_host(g_small):
    f = factorize_sequential(g_small, KEY)
    fwd_h, bwd_h = build_schedules(f)
    fwd_d, bwd_d = build_schedules_device(f)
    b = np.random.default_rng(4).normal(size=f.n).astype(np.float32)
    yp = np.asarray(kops.trisolve_panels(fwd_d, b))
    np.testing.assert_allclose(yp, solve_levels_np(fwd_h, b),
                               rtol=3e-4, atol=3e-4)
    B = np.random.default_rng(5).normal(size=(f.n, 3)).astype(np.float32)
    YP = np.asarray(kops.trisolve_panels(bwd_d, B, flip=True))
    for j in range(3):
        np.testing.assert_allclose(
            YP[:, j], solve_levels_np(bwd_h, B[:, j], flip=True),
            rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Batched multi-RHS PCG == independent single solves
# ---------------------------------------------------------------------------

def test_batched_pcg_matches_independent_solves(g_small, handle):
    g = g_small
    tol, maxiter = 1e-6, 300
    rng = np.random.default_rng(0)
    B = rng.normal(size=(8, g.n)).astype(np.float32)
    B -= B.mean(axis=1, keepdims=True)
    resB = handle.solve(jnp.asarray(B), tol=tol, maxiter=maxiter)
    assert bool(np.all(np.asarray(resB.converged)))
    for i in range(8):
        r1 = laplacian_pcg_jax(g, handle.precondition, jnp.asarray(B[i]),
                               tol=tol, maxiter=maxiter)
        # frozen-column batching keeps per-column trajectories independent;
        # batched reductions round differently, so a column sitting on the
        # tol boundary may stop one iteration apart — no more.
        assert abs(int(resB.iters[i]) - int(r1.iters)) <= 1
        assert float(resB.relres[i]) <= tol and float(r1.relres) <= tol
        assert abs(float(resB.relres[i]) - float(r1.relres)) < tol
        xb, x1 = np.asarray(resB.x[i], np.float64), np.asarray(r1.x,
                                                               np.float64)
        assert (np.linalg.norm(xb - x1) / np.linalg.norm(x1)) < 1e-2


def test_batched_pcg_heterogeneous_convergence(g_small, handle):
    """Columns with very different difficulty: easy ones freeze early."""
    g = g_small
    rng = np.random.default_rng(1)
    hard = rng.normal(size=g.n).astype(np.float32)
    hard -= hard.mean()
    easy = np.asarray(
        laplacian_matvec_np(g, rng.normal(size=g.n) * 1e-3)).astype(
        np.float32)
    easy -= easy.mean()
    B = jnp.asarray(np.stack([hard, easy * 0, easy]))
    res = handle.solve(B, tol=1e-6, maxiter=300)
    it = np.asarray(res.iters)
    assert it[1] == 0                     # zero rhs converges immediately
    assert bool(np.all(np.asarray(res.relres) <= 1e-6))


def test_batched_pcg_function_api(g_small):
    """laplacian_pcg_jax_batched with a vmapped preconditioner closure."""
    g = g_small
    f = factorize_wavefront(g, KEY, fill_slack=64)
    apply1 = make_preconditioner(f)
    B = np.random.default_rng(2).normal(size=(4, g.n)).astype(np.float32)
    B -= B.mean(axis=1, keepdims=True)
    res = laplacian_pcg_jax_batched(g, jax.vmap(apply1), jnp.asarray(B),
                                    tol=1e-6, maxiter=300)
    assert bool(np.all(np.asarray(res.converged)))
    for i in range(4):
        x = np.asarray(res.x[i], np.float64)
        r = B[i] - laplacian_matvec_np(g, x)
        assert np.linalg.norm(r) / np.linalg.norm(B[i]) < 5e-5


# ---------------------------------------------------------------------------
# Solver lifecycle
# ---------------------------------------------------------------------------

def test_solver_factor_solve_roundtrip(g_small, handle):
    g = g_small
    b = np.random.default_rng(3).normal(size=g.n).astype(np.float32)
    b -= b.mean()
    res = handle.solve(jnp.asarray(b), tol=1e-6, maxiter=300)
    assert bool(res.converged)
    x = np.asarray(res.x, np.float64)
    r = b - laplacian_matvec_np(g, x)
    assert np.linalg.norm(r) / np.linalg.norm(b) < 5e-5


def test_solver_matches_oracle_factor(g_small):
    s = Solver(chunk=32, fill_slack=64)
    h = s.factor(g_small, KEY)
    fs = factorize_sequential(g_small, KEY)
    assert np.array_equal(h.factor.rows, fs.rows)
    assert np.array_equal(h.factor.vals, fs.vals)


def test_solver_caches_jitted_solves(g_small, handle):
    handle._cache.clear()
    b = jnp.asarray(np.random.default_rng(4).normal(size=g_small.n),
                    jnp.float32)
    handle.solve(b)
    assert len(handle._cache) == 1
    handle.solve(b * 2.0)                       # same shape → cache hit
    assert len(handle._cache) == 1
    handle.solve(jnp.stack([b, b]))             # new batch shape
    assert len(handle._cache) == 2


def test_solver_rejects_bad_shapes(g_small, handle):
    with pytest.raises(ValueError):
        handle.solve(jnp.zeros((3, g_small.n + 1)))
    with pytest.raises(RuntimeError):
        Solver().solve(jnp.zeros(4))


def test_solver_keeps_single_handle(g_small):
    """Solver stays O(1) in device memory across a sweep of factors
    (FactorCache subclass with max_handles=1)."""
    s = Solver(chunk=32, fill_slack=64)
    s.factor(g_small, KEY)
    h2 = s.factor(graphs.grid2d(10, 10, seed=9), jax.random.key(1))
    assert len(s) == 1 and s.handle is h2


def test_solver_attach_host_factor(g_small):
    """attach() serves solves from a host-built (oracle) factor."""
    f = factorize_sequential(g_small, KEY)
    s = Solver()
    h = s.attach(g_small, f)
    b = np.random.default_rng(5).normal(size=g_small.n).astype(np.float32)
    b -= b.mean()
    res = h.solve(jnp.asarray(b), tol=1e-6, maxiter=300)
    assert bool(res.converged)


# ---------------------------------------------------------------------------
# Strict-overflow retry (satellite): tiny fill_slack forces slack doubling
# ---------------------------------------------------------------------------

def test_strict_overflow_retry_doubles_slack(g_small):
    # non-strict at slack 1 overflows — establishes the retry is needed
    f_loose = factorize_wavefront(g_small, KEY, chunk=32, fill_slack=1,
                                  strict=False)
    assert f_loose.stats["overflow"] > 0
    assert f_loose.stats["fill_slack"] == 1      # stats reflect final slack
    # strict mode re-runs with doubled slack until nothing is dropped
    f = factorize_wavefront(g_small, KEY, chunk=32, fill_slack=1,
                            strict=True)
    assert f.stats["overflow"] == 0
    slack = f.stats["fill_slack"]
    assert slack > 1 and (slack & (slack - 1)) == 0   # 1 doubled k times
    # retried factor is bit-identical to a generous-slack run
    f_ref = factorize_wavefront(g_small, KEY, chunk=32, fill_slack=64)
    assert np.array_equal(f.rows, f_ref.rows)
    assert np.array_equal(f.vals, f_ref.vals)
    assert np.array_equal(f.D, f_ref.D)


def test_strict_batched_retry_never_drops_fill(g_small):
    # the batched path retries only the overflowing graphs, as often as
    # it takes: a strict factor never carries dropped fill
    from repro.core.parac import factorize_batched
    gs = [g_small, graphs.grid2d(10, 14, seed=5)]
    keys = jnp.stack([KEY, jax.random.key(8)])
    fs = factorize_batched(gs, keys, chunk=32, fill_slack=1, strict=True)
    for g, f, key in zip(gs, fs, keys):
        assert f.stats["overflow"] == 0
        ref = factorize_wavefront(g, key, chunk=32, fill_slack=64)
        assert np.array_equal(f.rows, ref.rows)
        assert np.array_equal(f.vals, ref.vals)


# ---------------------------------------------------------------------------
# FactorHandle jit-cache keying (satellite): combos must not collide and
# the cache must stay bounded
# ---------------------------------------------------------------------------

def test_handle_jit_cache_keying(g_small, handle):
    handle._cache.clear()
    b = jnp.asarray(np.random.default_rng(6).normal(size=g_small.n),
                    jnp.float32)
    r_loose = handle.solve(b, tol=1e-3, maxiter=200)
    r_tight = handle.solve(b, tol=1e-6, maxiter=200)
    r_capped = handle.solve(b, tol=1e-6, maxiter=2)
    handle.solve(b, tol=1e-6, maxiter=200, project=False)
    assert len(handle._cache) == 4               # distinct combos, no collision
    # each combo kept its own semantics (a collision would reuse closures)
    assert int(r_tight.iters) > int(r_loose.iters)
    assert int(r_capped.iters) == 2 and not bool(r_capped.converged)
    assert float(r_tight.relres) <= 1e-6
    for _ in range(5):                           # repeats: hits, no growth
        handle.solve(b, tol=1e-3, maxiter=200)
        handle.solve(b, tol=1e-6, maxiter=200)
    assert len(handle._cache) == 4
    handle._cache.clear()


def test_handle_jit_cache_bounded_lru(g_small, handle):
    handle._cache.clear()
    old = handle.max_cached_solves
    handle.max_cached_solves = 3
    b = jnp.asarray(np.random.default_rng(7).normal(size=g_small.n),
                    jnp.float32)
    try:
        for i in range(6):
            handle.solve(b, tol=1e-6, maxiter=5 + i)
            assert len(handle._cache) <= 3
        # most recent combos survive, oldest were evicted
        kept = [k[3] for k in handle._cache]     # maxiter component
        assert kept == [8, 9, 10]
    finally:
        handle.max_cached_solves = old
        handle._cache.clear()

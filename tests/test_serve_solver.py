"""Multi-tenant solve service: batched fleet factorization
(``factorize_batched``), the ``FactorCache`` LRU, and the
continuous-batching ``SolveEngine``."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.parac import (factorize_wavefront, factorize_batched,
                              _next_pow2)
from repro.core.pcg import pcg_fleet_init
from repro.core.solver import FactorCache, graph_fingerprint
from repro.core.trisolve import build_schedules_device
from repro.serve import SolveEngine, SolveRequest
from repro.data import graphs


@pytest.fixture(scope="module")
def fleet():
    """Three graphs of different sizes (and their factorization keys)."""
    gs = {"g2d": graphs.grid2d(12, 12, seed=3),       # n = 144
          "pl": graphs.powerlaw(300, 5, seed=3),      # n = 300
          "road": graphs.road_like(10, seed=4)}       # n = 100
    keys = {name: jax.random.key(i) for i, name in enumerate(gs)}
    return gs, keys


@pytest.fixture(scope="module")
def cache(fleet):
    gs, keys = fleet
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor_batched(list(gs.values()), [keys[k] for k in gs],
                     graph_ids=list(gs))
    return c


def _rhs(rng, n, nrhs):
    b = rng.normal(size=(nrhs, n) if nrhs > 1 else n).astype(np.float32)
    return b - b.mean(axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# Batched fleet factorization == per-graph wavefront, bit for bit
# ---------------------------------------------------------------------------

def test_factorize_batched_bit_identical(fleet):
    gs, keys = fleet
    singles = {k: factorize_wavefront(g, keys[k], chunk=32, fill_slack=64)
               for k, g in gs.items()}
    batched = factorize_batched(list(gs.values()), [keys[k] for k in gs],
                                chunk=32, fill_slack=64)
    assert len({g.n for g in gs.values()}) == 3   # genuinely mixed sizes
    for (k, a), b in zip(singles.items(), batched):
        assert a.n == b.n and a.nnz == b.nnz
        assert np.array_equal(a.col_ptr, b.col_ptr)
        assert np.array_equal(a.rows, b.rows)
        assert np.array_equal(a.vals, b.vals)
        assert np.array_equal(a.D, b.D)
        assert b.stats["batched"] and b.stats["overflow"] == 0
        assert b.device is not None           # factor stays device-resident


def test_batched_schedules_match_per_factor_builder(fleet):
    """The one-shot vmapped schedule construction reproduces the
    per-factor device builder: same level structure for both triangular
    solves across a mixed-size fleet (backward levels are stored in
    original index space — the device builder's are flipped)."""
    gs, keys = fleet
    fs, scheds = factorize_batched(list(gs.values()),
                                   [keys[k] for k in gs],
                                   chunk=32, fill_slack=64,
                                   with_schedules=True)
    assert len(scheds) == len(fs)
    for f, (fwd_p, bwd_p) in zip(fs, scheds):
        fwd_d, bwd_d = build_schedules_device(f)
        n = f.n
        assert fwd_p.n == n and fwd_p.n_pad == _next_pow2(n)
        assert fwd_p.n_levels == fwd_d.n_levels
        assert bwd_p.n_levels == bwd_d.n_levels
        assert np.array_equal(np.asarray(fwd_p.level_of)[:n],
                              np.asarray(fwd_d.level_of))
        assert np.array_equal(np.asarray(bwd_p.level_of)[:n][::-1],
                              np.asarray(bwd_d.level_of))
        # phantom rows are level 0 with empty panels
        assert not np.any(np.asarray(fwd_p.level_of)[n:])
        assert not np.any(np.asarray(fwd_p.vals)[n:])
        # every solve edge is present: row sums of |vals| match the
        # factor's per-column absolute sums (fwd panels index by dst row)
        colsum = np.zeros(n, np.float64)
        np.add.at(colsum, f.rows, np.abs(f.vals.astype(np.float64)))
        rowsum = np.abs(np.asarray(fwd_p.vals, np.float64))[:n].sum(axis=1)
        np.testing.assert_allclose(rowsum, colsum, rtol=1e-6, atol=1e-6)


def test_factorize_batched_masked_retry(fleet):
    """Strict overflow handling in the batched path: overflowing graphs
    re-run at doubled slack while the result stays bit-identical to the
    generous-slack factorization."""
    gs, keys = fleet
    sub = [gs["g2d"], gs["road"]]
    ks = [keys["g2d"], keys["road"]]
    ref = factorize_batched(sub, ks, chunk=32, fill_slack=64)
    low = factorize_batched(sub, ks, chunk=32, fill_slack=1)
    assert any(b.stats["fill_slack"] > 1 for b in low)   # retry happened
    for a, b in zip(ref, low):
        assert b.stats["overflow"] == 0
        assert np.array_equal(a.rows, b.rows)
        assert np.array_equal(a.vals, b.vals)
        assert np.array_equal(a.D, b.D)


def test_fleet_admit_many_bit_identical_to_sequential(fleet):
    """Satellite: ``FactorFleet.admit_many`` (grow the bucket stack once,
    scatter all B rows in one update) leaves every fleet bit-identical
    to B sequential ``admit`` calls — same rows, same padded envelopes,
    same stacked arrays — across a batch that mixes two same-bucket
    factors with a different-bucket one."""
    gs, keys = fleet
    g_b = graphs.grid2d(12, 12, seed=8)       # same bucket as g2d, new factor
    batch = [("g2d", gs["g2d"], keys["g2d"]),
             ("g2d_b", g_b, jax.random.key(9)),
             ("road", gs["road"], keys["road"])]
    seq = FactorCache(chunk=32, fill_slack=64)
    for name, g, k in batch:                  # one admit per factor
        seq.factor(g, k, graph_id=name)
    bat = FactorCache(chunk=32, fill_slack=64)
    bat.factor_batched([g for _, g, _ in batch],
                       [k for _, _, k in batch],
                       graph_ids=[name for name, _, _ in batch])
    assert seq.fleets.keys() == bat.fleets.keys()
    for name, _, _ in batch:
        assert seq.get(name).fleet_row == bat.get(name).fleet_row
    for n_pad, fs in seq.fleets.items():
        fb = bat.fleets[n_pad]
        assert (fs.m_pad, fs.Kf, fs.Kb) == (fb.m_pad, fb.Kf, fb.Kb)
        assert (fs.f_levels, fs.b_levels) == (fb.f_levels, fb.b_levels)
        assert fs.capacity == fb.capacity
        for field, a, b in zip(fs.arrays._fields, fs.arrays, fb.arrays):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                (n_pad, field)
    # and the solves they serve are byte-for-byte the same
    rng = np.random.default_rng(29)
    b = jnp.asarray(_rhs(rng, gs["g2d"].n, 2))
    ra = seq.solve("g2d_b", b, tol=1e-6, maxiter=300)
    rb = bat.solve("g2d_b", b, tol=1e-6, maxiter=300)
    assert np.array_equal(np.asarray(ra.x), np.asarray(rb.x))
    assert np.array_equal(np.asarray(ra.iters), np.asarray(rb.iters))


def test_factorize_batched_key_count_mismatch(fleet):
    gs, keys = fleet
    with pytest.raises(ValueError):
        factorize_batched([gs["g2d"]], [keys["g2d"], keys["road"]])


# ---------------------------------------------------------------------------
# FactorCache: fingerprints, routing, LRU, memory budget
# ---------------------------------------------------------------------------

def test_graph_fingerprint_content_keyed():
    g = graphs.grid2d(8, 8, seed=0)
    same = graphs.grid2d(8, 8, seed=0)
    other = graphs.grid2d(8, 8, seed=1)
    assert graph_fingerprint(g) == graph_fingerprint(same)
    assert graph_fingerprint(g) != graph_fingerprint(other)
    k0, k1 = jax.random.key(0), jax.random.key(1)
    assert graph_fingerprint(g, k0) != graph_fingerprint(g, k1)


def test_factor_cache_hits_and_routing(fleet, cache):
    gs, keys = fleet
    h = cache.get("g2d")
    hits = cache.hits
    assert cache.factor(gs["g2d"], keys["g2d"], graph_id="g2d") is h
    assert cache.hits == hits + 1
    again = cache.factor_batched(list(gs.values()),
                                 [keys[k] for k in gs], graph_ids=list(gs))
    assert again[0] is h and cache.hits == hits + 4
    res = cache.solve("g2d", jnp.asarray(_rhs(np.random.default_rng(0),
                                              gs["g2d"].n, 1)),
                      tol=1e-6, maxiter=300)
    assert bool(res.converged)
    with pytest.raises(KeyError):
        cache.get("unknown-graph")


def test_factor_cache_lru_eviction(fleet):
    gs, keys = fleet
    c = FactorCache(chunk=32, fill_slack=64, max_handles=2)
    for name, g in gs.items():
        c.factor(g, keys[name], graph_id=name)
    assert len(c) == 2 and "g2d" not in c and c.evictions == 1
    c.get("pl")                             # touch: pl becomes most recent
    c.factor(gs["g2d"], keys["g2d"], graph_id="g2d")
    assert "pl" in c and "g2d" in c and "road" not in c


def test_factor_cache_memory_budget(fleet, cache):
    gs, keys = fleet
    bytes_g2d = cache.get("g2d").device_bytes
    assert bytes_g2d > 0
    c = FactorCache(chunk=32, fill_slack=64,
                    memory_budget_bytes=bytes_g2d + 1)
    c.factor(gs["g2d"], keys["g2d"], graph_id="a")
    c.factor(gs["road"], keys["road"], graph_id="b")
    assert "b" in c and "a" not in c and c.evictions == 1
    stats = c.stats()
    assert stats["handles"] == 1 and stats["device_bytes"] <= bytes_g2d + 1


# ---------------------------------------------------------------------------
# SolveEngine: drain semantics, continuous batching, mixed trace
# ---------------------------------------------------------------------------

def test_engine_rejects_bad_requests(cache):
    eng = SolveEngine(cache, slots=2)
    n = cache.get("g2d").n
    with pytest.raises(KeyError):
        eng.submit(SolveRequest(rid=0, graph_id="nope", b=np.zeros(4)))
    with pytest.raises(ValueError):        # wider than the engine
        eng.submit(SolveRequest(rid=1, graph_id="g2d", b=np.zeros((3, n))))
    with pytest.raises(ValueError):        # wrong n
        eng.submit(SolveRequest(rid=2, graph_id="g2d", b=np.zeros(n + 1)))
    with pytest.raises(ValueError):        # empty rhs block
        eng.submit(SolveRequest(rid=3, graph_id="g2d",
                                b=np.zeros((0, n), np.float32)))
    assert not eng._pinned                 # rejected submits pin nothing


def test_engine_drain_returns_completed(cache):
    """Satellite: ``run_until_drained`` must hand back every finished
    request (the seed engine silently dropped them)."""
    rng = np.random.default_rng(5)
    n = cache.get("g2d").n
    eng = SolveEngine(cache, slots=2, iters_per_tick=8)
    reqs = [SolveRequest(rid=i, graph_id="g2d", b=_rhs(rng, n, 1),
                         tol=1e-6, maxiter=300) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    assert eng.busy
    done = eng.run_until_drained()
    assert {r.rid for r in done} == {0, 1, 2}
    assert not eng.busy and all(lane is None for lane in eng.lanes)
    assert eng.run_until_drained() == []       # idempotent once drained
    assert list(eng.completed) == done         # bounded history deque
    # completed requests release their factor ref: the bounded history
    # must not keep evicted handles' fleet rows claimed
    assert all(r._handle is None for r in done)
    for r in reqs:
        assert r.converged and r.x is not None
        assert r.finish_tick >= r.admit_tick >= r.submit_tick >= 0
        assert float(r.relres[0]) <= r.tol


def test_engine_survives_cache_eviction(fleet, cache):
    """In-flight requests pin their handle: evicting the graph from the
    cache after submit must not crash the drain or corrupt results."""
    gs, keys = fleet
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor(gs["g2d"], keys["g2d"], graph_id="g2d")
    eng = SolveEngine(c, slots=2, iters_per_tick=8)
    rng = np.random.default_rng(9)
    req = SolveRequest(rid=0, graph_id="g2d", b=_rhs(rng, gs["g2d"].n, 1),
                       tol=1e-6, maxiter=300)
    eng.submit(req)
    c.evict("g2d")                          # gone from the cache...
    done = eng.run_until_drained()
    assert done == [req] and req.converged  # ...but the solve completes
    assert not eng._pinned                  # idle engine pins nothing
    with pytest.raises(KeyError):           # new submits do fail-fast
        eng.submit(SolveRequest(rid=1, graph_id="g2d",
                                b=_rhs(rng, gs["g2d"].n, 1)))


def test_engine_submit_routes_to_reattached_factor(fleet):
    """Re-attaching a graph_id to a *different* factor mid-flight: new
    submits route to the new factor immediately, while the in-flight
    request keeps solving against the handle it was submitted with
    (its own strong ref keeps the old fleet row alive)."""
    gs, keys = fleet
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor(gs["road"], keys["road"], graph_id="g")        # n = 100
    eng = SolveEngine(c, slots=2, iters_per_tick=4)
    rng = np.random.default_rng(31)
    r_old = SolveRequest(rid=0, graph_id="g", b=_rhs(rng, gs["road"].n, 1),
                         tol=1e-6, maxiter=300)
    eng.submit(r_old)
    f2 = factorize_wavefront(gs["g2d"], keys["g2d"], chunk=32,
                             fill_slack=64)
    c.attach(gs["g2d"], f2, graph_id="g")                   # n = 144
    r_new = SolveRequest(rid=1, graph_id="g", b=_rhs(rng, gs["g2d"].n, 1),
                         tol=1e-6, maxiter=300)
    eng.submit(r_new)            # validates against the NEW factor's n
    done = eng.run_until_drained()
    assert {r.rid for r in done} == {0, 1}
    assert r_old.converged and r_old.x.shape == (gs["road"].n,)
    assert r_new.converged and r_new.x.shape == (gs["g2d"].n,)


def test_engine_zero_rhs_retires_immediately(cache):
    eng = SolveEngine(cache, slots=2)
    n = cache.get("g2d").n
    req = SolveRequest(rid=0, graph_id="g2d", b=np.zeros(n, np.float32))
    eng.submit(req)
    done = eng.run_until_drained(max_ticks=3)
    assert done == [req] and req.converged and int(req.iters[0]) == 0


def test_engine_mixed_trace_bit_exact_vs_direct(fleet, cache):
    """Acceptance: the device-resident engine reproduces direct
    ``FactorHandle.solve`` results **bit-exactly** over the mixed
    8-request / 3-graph suite (both paths run the same fleet PCG body
    over the same stacked bucket arrays), while the recompile counter
    shows one step program per shape bucket — not per factor — and
    per-tick host transfers are O(admitted + retired) columns."""
    gs, _ = fleet
    rng = np.random.default_rng(11)
    eng = SolveEngine(cache, slots=6, iters_per_tick=8)
    spec = [("g2d", 1, 1e-6), ("pl", 2, 1e-5), ("road", 1, 1e-6),
            ("g2d", 3, 1e-6), ("pl", 1, 1e-6), ("road", 2, 1e-5),
            ("g2d", 1, 1e-4), ("pl", 2, 1e-6)]
    reqs = [SolveRequest(rid=i, graph_id=gid, b=_rhs(rng, gs[gid].n, nr),
                         tol=tol, maxiter=500)
            for i, (gid, nr, tol) in enumerate(spec)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert len(done) == len(reqs)
    for r in reqs:
        ref = cache.get(r.graph_id).solve(jnp.asarray(np.atleast_2d(r.b)),
                                          tol=r.tol, maxiter=r.maxiter)
        assert np.array_equal(np.atleast_2d(r.x), np.asarray(ref.x))
        assert np.array_equal(np.atleast_1d(r.iters),
                              np.asarray(ref.iters))
        assert np.array_equal(np.atleast_1d(r.relres),
                              np.atleast_1d(np.asarray(ref.relres)))
    st = eng.stats()
    # one compiled step program per shape bucket (3 distinct n_pads here)
    assert st.buckets == len({_next_pow2(g.n) for g in gs.values()})
    assert st.step_compiles == st.buckets
    # host↔device column traffic == admitted + retired columns exactly
    total_cols = sum(r.nrhs for r in reqs)
    assert st.cols_in == total_cols and st.cols_out == total_cols


def test_engine_shape_bucket_mega_batch(fleet):
    """Two *different* factors whose graphs share a shape bucket tick
    through one compiled step program in the same jitted call, and each
    still reproduces its own direct solve bit-exactly."""
    g_a = graphs.grid2d(12, 12, seed=3)
    g_b = graphs.grid2d(12, 12, seed=8)        # same n/m, different weights
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor_batched([g_a, g_b], [jax.random.key(0), jax.random.key(1)],
                     graph_ids=["a", "b"])
    ha, hb = c.get("a"), c.get("b")
    assert ha.fleet is hb.fleet                # same bucket fleet
    assert ha.fleet_row != hb.fleet_row
    eng = SolveEngine(c, slots=4, iters_per_tick=8)
    rng = np.random.default_rng(13)
    ra = SolveRequest(rid=0, graph_id="a", b=_rhs(rng, g_a.n, 2),
                      tol=1e-6, maxiter=300)
    rb = SolveRequest(rid=1, graph_id="b", b=_rhs(rng, g_b.n, 2),
                      tol=1e-6, maxiter=300)
    eng.submit(ra)
    eng.submit(rb)
    done = eng.run_until_drained()
    assert len(done) == 2
    st = eng.stats()
    assert st.buckets == 1 and st.step_compiles == 1   # shared program
    for r, h in ((ra, ha), (rb, hb)):
        ref = h.solve(jnp.asarray(np.atleast_2d(r.b)), tol=r.tol,
                      maxiter=r.maxiter)
        assert r.converged
        assert np.array_equal(np.atleast_2d(r.x), np.asarray(ref.x))
        assert np.array_equal(np.atleast_1d(r.iters),
                              np.asarray(ref.iters))


@pytest.mark.parametrize("graphs_in_cache", [1, 2])
def test_engine_counts_dispatches_by_index_path(graphs_in_cache):
    """An 8-slot engine over a one-graph cache (a one-row fleet) steps on
    the shared-index path only, and each answer is bit-identical to
    ``handle.solve`` of its right-hand side; over two graphs of one
    bucket (a two-row fleet) it steps on the per-lane path, with the
    same answers.  The counts show in ``stats()``, in the metrics
    registry and on the handle."""
    from repro.obs.registry import MetricsRegistry
    gs = [graphs.grid2d(12, 12, seed=3), graphs.grid2d(12, 12, seed=8)]
    gs = gs[:graphs_in_cache]
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor_batched(gs, [jax.random.key(i) for i in range(len(gs))],
                     graph_ids=[f"g{i}" for i in range(len(gs))])
    hs = [c.get(f"g{i}") for i in range(len(gs))]
    assert hs[0].fleet.capacity == graphs_in_cache
    reg = MetricsRegistry()
    eng = SolveEngine(c, slots=8, iters_per_tick=8, metrics=reg)
    rng = np.random.default_rng(17)
    reqs = [SolveRequest(rid=i, graph_id=f"g{i % len(gs)}",
                         b=_rhs(rng, gs[0].n, 1), tol=1e-6, maxiter=300)
            for i in range(8)]
    for r in reqs:
        eng.submit(r)
    assert len(eng.run_until_drained()) == len(reqs)
    st = eng.stats()
    path = "shared" if graphs_in_cache == 1 else "per_lane"
    counts = {"shared": st.shared_dispatches,
              "per_lane": st.per_lane_dispatches}
    assert counts[path] > 0 and sum(counts.values()) == counts[path]
    m = reg.counter("repro_fleet_dispatch_total",
                    labels=("replica", "path"))
    assert {p: m.labels(replica="solo", path=p).value
            for p in counts} == counts
    for i, r in enumerate(reqs):
        h = hs[i % len(gs)]
        ref = h.solve(jnp.asarray(r.b), tol=r.tol, maxiter=r.maxiter)
        assert r.converged
        assert np.array_equal(r.x, np.asarray(ref.x))
        assert np.array_equal(np.atleast_1d(r.iters),
                              np.atleast_1d(np.asarray(ref.iters)))
        assert np.array_equal(np.atleast_1d(r.relres),
                              np.atleast_1d(np.asarray(ref.relres)))
    # one-column direct solves read one row whatever the fleet holds
    assert hs[0].dispatches["shared"] >= 1
    assert hs[0].dispatches["per_lane"] == 0


def test_engine_scatter_admission_matches_host_oracle(fleet, cache):
    """Satellite: the jitted scatter admission leaves bit-identical
    per-lane carries to a host-stacked oracle that initializes the same
    columns directly with ``pcg_fleet_init`` and places them row by
    row."""
    gs, _ = fleet
    h = cache.get("g2d")
    fleet_ = h.fleet
    rng = np.random.default_rng(17)
    b = _rhs(rng, gs["g2d"].n, 3)
    eng = SolveEngine(cache, slots=4, iters_per_tick=8)
    req = SolveRequest(rid=0, graph_id="g2d", b=b, tol=1e-6, maxiter=300)
    eng.submit(req)
    eng._admit()                               # scatter path, no stepping
    bl = eng._buckets[(fleet_.family, fleet_.n_pad, fleet_.k_tier)]
    # host oracle: same init math on the stacked columns
    Bp = np.zeros((4, fleet_.n_pad), np.float32)    # pow2-padded like admit
    Bp[:3, :h.n] = b
    fidx = np.zeros(4, np.int32)
    fidx[:3] = h.fleet_row
    oracle_init = jax.jit(pcg_fleet_init,
                          static_argnames=("f_levels", "b_levels"))
    oracle = oracle_init(
        fleet_.arrays, jnp.asarray(fidx), jnp.asarray(Bp),
        jnp.asarray(np.array([1e-6] * 3 + [1.0], np.float32)),
        jnp.asarray(np.array([300] * 3 + [0], np.int32)),
        f_levels=fleet_.f_levels, b_levels=fleet_.b_levels)
    rows = [i for i, lane in enumerate(eng.lanes) if lane is not None]
    assert rows == [0, 1, 2]
    for name in ("X", "R", "Z", "P"):
        got = np.asarray(getattr(bl.state, name))[rows]
        want = np.asarray(getattr(oracle, name))[:3]
        assert np.array_equal(got, want), name
    for name in ("rz", "it", "active", "bnorm", "tol", "maxiter"):
        got = np.asarray(getattr(bl.state, name))[rows]
        want = np.asarray(getattr(oracle, name))[:3]
        assert np.array_equal(got, want), name


def test_factor_cache_ttl_expires_stale_handles(fleet):
    """Satellite: per-handle ``ttl_s`` against an injected clock — a
    resubmitted modified graph's ancestor ages out instead of
    accumulating under the budget; no wall-time reads involved."""
    gs, keys = fleet
    now = [0.0]
    c = FactorCache(chunk=32, fill_slack=64, clock=lambda: now[0])
    c.factor(gs["g2d"], keys["g2d"], graph_id="old", ttl_s=10.0)
    c.factor(gs["road"], keys["road"], graph_id="keep")   # no ttl: immortal
    assert "old" in c and "keep" in c
    now[0] = 5.0
    assert c.factor(gs["g2d"], keys["g2d"], graph_id="old").graph_id == "old"
    assert c.hits >= 1                        # fresh → still a cache hit
    # explicit ttl on a hit re-admits: birth resets, policy replaced
    h = c.factor(gs["g2d"], keys["g2d"], graph_id="old", ttl_s=10.0)
    assert h.born_s == 5.0
    now[0] = 11.0                             # 6s after refresh: still fresh
    c.sweep_stale()
    assert "old" in c
    now[0] = 16.0                             # 11s after refresh: stale
    c.sweep_stale()
    assert "old" not in c and "keep" in c
    assert c.stats()["expirations"] == 1
    # resubmission after expiry is a miss → re-factors cleanly
    misses = c.misses
    c.factor(gs["g2d"], keys["g2d"], graph_id="old", ttl_s=10.0)
    assert c.misses == misses + 1 and "old" in c


def test_factor_cache_max_age_ticks(fleet, cache):
    """Satellite: ``max_age_ticks`` staleness driven by the engine's
    tick clock (``advance_ticks``), no wall time involved."""
    gs, keys = fleet
    c = FactorCache(chunk=32, fill_slack=64)
    c.factor(gs["road"], keys["road"], graph_id="aging", max_age_ticks=3)
    eng = SolveEngine(c, slots=2, iters_per_tick=4)
    rng = np.random.default_rng(23)
    req = SolveRequest(rid=0, graph_id="aging",
                       b=_rhs(rng, gs["road"].n, 1), tol=1e-6, maxiter=300)
    eng.submit(req)
    done = eng.run_until_drained()            # engine advances cache ticks
    assert done == [req] and req.converged    # in-flight work unaffected
    assert c.now_ticks == eng.ticks
    if c.now_ticks <= 3:                      # drain was short: age it out
        c.advance_ticks(4)
    with pytest.raises(KeyError):             # stale → swept on lookup
        c.get("aging")
    assert c.stats()["expirations"] == 1


def test_engine_mixed_trace_matches_direct_solves(fleet, cache):
    """Acceptance: ≥ 3 graphs, ≥ 8 interleaved requests, single- and
    multi-RHS — every request's residuals/iterates match a direct
    ``FactorHandle.solve`` of the same rhs block."""
    gs, _ = fleet
    rng = np.random.default_rng(7)
    eng = SolveEngine(cache, slots=6, iters_per_tick=8)
    spec = [("g2d", 1, 1e-6), ("pl", 2, 1e-5), ("road", 1, 1e-6),
            ("g2d", 3, 1e-6), ("pl", 1, 1e-6), ("road", 2, 1e-5),
            ("g2d", 1, 1e-4), ("pl", 2, 1e-6)]
    reqs = [SolveRequest(rid=i, graph_id=gid, b=_rhs(rng, gs[gid].n, nr),
                         tol=tol, maxiter=500)
            for i, (gid, nr, tol) in enumerate(spec)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert len(done) == len(reqs)
    for r in reqs:
        handle = cache.get(r.graph_id)
        ref = handle.solve(jnp.asarray(np.atleast_2d(r.b)), tol=r.tol,
                           maxiter=r.maxiter)
        assert r.converged and bool(np.all(np.asarray(ref.converged)))
        relres = np.atleast_1d(r.relres)
        ref_rr = np.atleast_1d(np.asarray(ref.relres))
        assert np.all(relres <= r.tol)
        np.testing.assert_allclose(relres, ref_rr, rtol=1e-3, atol=1e-12)
        # frozen-lane batching: per-column trajectories are independent of
        # batch composition — iterates line up with the direct solve
        assert np.all(np.abs(np.atleast_1d(r.iters)
                             - np.atleast_1d(np.asarray(ref.iters))) <= 1)
        X = np.atleast_2d(r.x)
        Xr = np.atleast_2d(np.asarray(ref.x))
        for j in range(X.shape[0]):
            denom = max(np.linalg.norm(Xr[j]), 1e-12)
            assert np.linalg.norm(X[j] - Xr[j]) / denom < 1e-2
    # continuous batching actually interleaved factors within single ticks
    assert eng.ticks < sum(int(np.max(np.atleast_1d(r.iters))) for r in reqs)

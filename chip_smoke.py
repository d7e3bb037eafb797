#!/usr/bin/env python3
"""Chip smoke test: drive the factor → serve path once on a TPU and check
what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # SolveCluster over four chips only

One process, no children.  The two largest 3D/2D graphs of
``graphs.SUITE_LARGE`` (``grid3d_contrast_32``, n = 32,768, the paper's
Table-1 high-contrast 3D Poisson family; ``grid2d_256``, n = 65,536) are
generated from their seeds, relabelled by the ``nnz-sort`` elimination
ordering (the paper's best GPU ordering and ``launch/solve.py``'s
default), factored through ``FactorCache.factor`` and solved:

* ``single``: family ``ac`` per graph, a direct ``handle.solve`` at
  tol 1e-6, maxiter 500 — factor, first-call (compile included) and
  warm-call seconds;
* ``served``: seeded requests (nrhs 1–4) through ``SolveFrontend`` over
  ``SolveEngine``, against the ``ac`` handles and one ``spai`` handle
  (the ``kind="spmv"`` apply; ``amg`` materializes a dense n × n
  operator on the host, which these sizes cannot hold);
* ``four-chips`` (only with ``--four-chips``): a ``SolveCluster`` of
  three solve replicas and one factor replica, one per chip, replaying
  a skewed trace, then a one-replica cluster replaying the same trace.

Every result must be converged and bitwise equal to a direct
``handle.solve`` on the handle that served it, and its true relative
residual — recomputed on the host in float64 from the graph's edge
list — must be within 10 × max(tol, float32 floor).  The float32 floor
is the host residual of the exact (float64 SuperLU) solution rounded to
float32: on the high-contrast graph it is already ~4e-6, so no float32
iterate can be held to 10 × 1e-6 alone.  Any failure exits non-zero
before the result line.  Printed times are smoke times from a single
run, not a benchmark.

The script exits non-zero, printing no result, when JAX finds no TPU or
when the repository's ``src/`` is not beside it.  On success the last
line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
GRAPHS = ("grid3d_contrast_32", "grid2d_256")
TOL = 1e-6
MAXITER = 500
# host-checked residual bound: RESIDUAL_SLACK x max(tol, float32 floor)
RESIDUAL_SLACK = 10.0
SERVED_LOOSE_TOL = 1e-4      # the serve launcher's other trace tolerance
# FSAI needs hundreds of iterations on these graphs; at 1e-6 its float32
# recurrence residual drifts far below the true one, so spai is asked 1e-4
SERVED_SPAI_TOL = 1e-4
SLOTS = 8                    # engine lanes (SolveEngine's default)
# strict construction with room for the sampled fill of these graphs
# (one run, no slack-doubling recompiles)
CACHE_KW = dict(fill_slack=256)
SEED = 0
# a chip run of this script is held to 1,200 s; a little before that
# every thread's stack goes to stderr, so a stopped run says where it was
STACKS_AFTER_S = 1100


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:8.1f}s] {msg}", flush=True)


# -- host-side reference ----------------------------------------------------

def host_relres(g, x: np.ndarray, b: np.ndarray) -> float:
    """True relative residual ``‖P b − L x‖ / ‖P b‖`` in float64 from the
    graph's edge list (``P`` the mean-zero projection the solver
    applies to a Laplacian rhs)."""
    b = np.asarray(b, np.float64)
    b = b - b.mean()
    x = np.asarray(x, np.float64)
    diff = np.asarray(g.w, np.float64) * (x[g.src] - x[g.dst])
    lx = (np.bincount(g.src, weights=diff, minlength=g.n)
          - np.bincount(g.dst, weights=diff, minlength=g.n))
    return float(np.linalg.norm(b - lx) / max(np.linalg.norm(b), 1e-300))


class Reference:
    """Float64 direct solve of a graph's grounded Laplacian (scipy
    SuperLU), the host reference every device result is held to."""

    def __init__(self, g):
        import scipy.sparse as sp
        import scipy.sparse.linalg as spl
        w = np.asarray(g.w, np.float64)
        a = sp.coo_matrix((np.r_[w, w], (np.r_[g.src, g.dst],
                                         np.r_[g.dst, g.src])),
                          shape=(g.n, g.n)).tocsr()
        lap = sp.diags(np.asarray(a.sum(axis=1)).ravel()) - a
        self.g = g
        self._lu = spl.splu(lap[1:, 1:].tocsc())

    def solve(self, b: np.ndarray) -> np.ndarray:
        b = np.asarray(b, np.float64)
        b = b - b.mean()
        x = np.zeros(self.g.n)
        x[1:] = self._lu.solve(b[1:])
        return x - x.mean()

    def float32_floor(self, b: np.ndarray) -> float:
        """Host residual of the exact solution rounded to float32: no
        float32 iterate does much better on this graph."""
        return host_relres(self.g, self.solve(b).astype(np.float32), b)


def load_graph(name: str):
    from repro.core.ordering import ORDERINGS
    from repro.data import graphs
    g = graphs.SUITE_LARGE[name]()
    return g.permute(ORDERINGS["nnz-sort"](g, seed=SEED)).coalesce()


def rhs(rng, n: int, nrhs: int = 0) -> np.ndarray:
    shape = (n,) if nrhs == 0 else (nrhs, n)
    b = rng.normal(size=shape).astype(np.float32)
    return b - b.mean(axis=-1, keepdims=True)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def check_result(tag: str, g, x, b, converged, relres, *, tol: float,
                 floor: float) -> float:
    """Converged flag plus the host float64 residual of every column,
    within ``RESIDUAL_SLACK`` × the larger of ``tol`` and the graph's
    float32 floor."""
    xs, bs = np.atleast_2d(x), np.atleast_2d(b)
    conv = np.atleast_1d(np.asarray(converged))
    check(bool(np.all(conv)), f"{tag}: not converged "
          f"(relres {np.atleast_1d(np.asarray(relres)).tolist()})")
    worst = max(host_relres(g, xi, bi) for xi, bi in zip(xs, bs))
    bound = RESIDUAL_SLACK * max(tol, floor)
    check(np.isfinite(worst) and worst <= bound,
          f"{tag}: host residual {worst!r} > {RESIDUAL_SLACK} x "
          f"max(tol={tol!r}, float32 floor={floor!r})")
    return worst


def check_bitwise(served) -> None:
    """Every served result is bitwise equal to a direct ``handle.solve``
    of its columns.  ``served`` holds ``(tag, handle, tol, b, req)``.
    Columns sharing a handle and a tol are solved together in blocks of
    ``SLOTS`` (zero columns pad the last block; a zero rhs freezes at
    once): the engine's own lane count, so both sides run the same
    shapes, and one compile per group.  Lanes are independent, so a
    block's other columns cannot change a lane."""
    groups = {}
    for tag, h, tol, b, req in served:
        groups.setdefault((id(h), tol), (h, tol, []))[2].append(
            (tag, req, np.atleast_2d(b)))
    for h, tol, items in groups.values():
        cols = np.concatenate([b for _, _, b in items])
        x, iters = [], []
        for at in range(0, len(cols), SLOTS):
            block = np.zeros((SLOTS, cols.shape[1]), cols.dtype)
            chunk = cols[at:at + SLOTS]
            block[:len(chunk)] = chunk
            ref = h.solve(block, tol=tol, maxiter=MAXITER)
            x.append(np.asarray(ref.x)[:len(chunk)])
            iters.append(np.asarray(ref.iters)[:len(chunk)])
        x, iters = np.concatenate(x), np.concatenate(iters)
        at = 0
        for tag, req, b in items:
            sl = slice(at, at + len(b))
            check(np.array_equal(np.atleast_2d(req.x), x[sl]),
                  f"{tag}: served iterate differs from a direct "
                  f"handle.solve")
            check(np.array_equal(np.atleast_1d(req.iters), iters[sl]),
                  f"{tag}: served iteration counts differ from a direct "
                  f"handle.solve")
            at += len(b)


# -- phases -----------------------------------------------------------------

def spmv_implementation(dev) -> str:
    """What the compiler makes of the served fleet SpMV on ``dev``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.kernels.ops import ell_spmv_fleet
    sh = SingleDeviceSharding(dev)
    spec = [jax.ShapeDtypeStruct((8, 1024, 128), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((8, 1024, 128), jnp.float32, sharding=sh),
            jax.ShapeDtypeStruct((8, 1024), jnp.float32, sharding=sh)]
    text = ell_spmv_fleet.lower(*spec).compile().as_text()
    if "tpu_custom_call" in text:
        return "pallas kernel (Mosaic custom call)"
    return ("xla gather-multiply-reduce, no Pallas custom call "
            "(repro.kernels.ops.ell_spmv_fleet)")


class Graphs:
    """The smoke's graphs, each with its host reference and the float32
    floor of its residual (measured on one seeded rhs)."""

    def __init__(self, names):
        self.g = {name: load_graph(name) for name in names}
        self.ref = {name: Reference(g) for name, g in self.g.items()}
        floor_rng = np.random.default_rng(SEED + 1)
        self.floor = {name: self.ref[name].float32_floor(
            rhs(floor_rng, g.n)) for name, g in self.g.items()}

    def check(self, tag, name, x, b, converged, relres, tol) -> float:
        return check_result(tag, self.g[name], x, b, converged, relres,
                            tol=tol, floor=self.floor[name])


def phase_single(cache, gs: Graphs, dev, rng) -> None:
    """Factor (family ac) and direct-solve each graph at tol 1e-6."""
    import jax
    import jax.numpy as jnp
    for name, g in gs.g.items():
        t0 = time.perf_counter()
        h = cache.factor(g, jax.random.key(SEED), graph_id=f"{name}/ac",
                         family="ac")
        jax.block_until_ready(h.fleet.arrays)
        factor_s = time.perf_counter() - t0
        log(f"[single] {name}: factored, levels={h.n_levels_fwd}/"
            f"{h.n_levels_bwd} K_tier={h.fleet.k_tier} | smoke times: "
            f"factor_s={factor_s!r}")
        b = jnp.asarray(rhs(rng, g.n))
        t0 = time.perf_counter()
        r1 = h.solve(b, tol=TOL, maxiter=MAXITER)
        jax.block_until_ready(r1.x)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        r2 = h.solve(b, tol=TOL, maxiter=MAXITER)
        jax.block_until_ready(r2.x)
        warm_s = time.perf_counter() - t0
        worst = gs.check(f"single {name}", name, r1.x, b, r1.converged,
                         r1.relres, TOL)
        check(np.array_equal(np.asarray(r1.x), np.asarray(r2.x)),
              f"single {name}: two identical solves differ")
        f = h.factor
        log(f"[single] {name}: n={g.n} m={g.m} nnz(G)={f.nnz} "
            f"levels={h.n_levels_fwd}/{h.n_levels_bwd} "
            f"K_tier={h.fleet.k_tier} overflow={f.stats['overflow']} "
            f"fill_slack={f.stats['fill_slack']} iters={int(r1.iters)} "
            f"relres={float(r1.relres)!r} host_relres={worst!r} "
            f"float32_floor={gs.floor[name]!r} | smoke times: "
            f"factor_s={factor_s!r} first_call_s={first_s!r} "
            f"warm_call_s={warm_s!r} peak_bytes_in_use={peak_bytes(dev)}")


def phase_served(cache, gs: Graphs, dev, rng, n_requests: int) -> None:
    """Seeded requests through SolveFrontend over SolveEngine: the ac
    handles of both graphs plus one spai handle (the ``spmv`` apply)."""
    import jax
    from repro.serve import SolveEngine, SolveFrontend
    spai_graph = GRAPHS[0]
    t0 = time.perf_counter()
    h = cache.factor(gs.g[spai_graph], jax.random.key(SEED),
                     graph_id=f"{spai_graph}/spai", family="spai")
    jax.block_until_ready(h.fleet.arrays)
    log(f"[served] {spai_graph}/spai: K_tier={h.fleet.k_tier} | smoke "
        f"times: factor_s={time.perf_counter() - t0!r}")
    # (graph, family, tol): the ac requests alternate the serve
    # launcher's two tolerances; spai asks 1e-4 (see SERVED_SPAI_TOL)
    mix = [(GRAPHS[0], "ac", TOL), (GRAPHS[1], "ac", SERVED_LOOSE_TOL),
           (spai_graph, "spai", SERVED_SPAI_TOL),
           (GRAPHS[0], "ac", SERVED_LOOSE_TOL), (GRAPHS[1], "ac", TOL)]
    reqs = []
    for i in range(n_requests):
        name, fam, tol = mix[i % len(mix)]
        nrhs = int(rng.integers(1, 5))
        b = rhs(rng, gs.g[name].n, 0 if nrhs == 1 else nrhs)
        reqs.append((name, f"{name}/{fam}", tol, b))
    engine = SolveEngine(cache, slots=SLOTS, iters_per_tick=8)
    t0 = time.perf_counter()
    with SolveFrontend(engine, max_queue=4 * n_requests) as fe:
        futs = [fe.submit(gid, b, tol=tol, maxiter=MAXITER)
                for _, gid, tol, b in reqs]
        done = [fut.result() for fut in futs]     # re-raises any failure
    served_s = time.perf_counter() - t0
    check(fe.driver_error is None,
          f"served: driver crashed: {fe.driver_error!r}")
    served = []
    for (name, gid, tol, b), req in zip(reqs, done):
        tag = f"served rid={req.rid} {gid} nrhs={req.nrhs} tol={tol}"
        check(req.status == "converged", f"{tag}: status {req.status!r}")
        worst = gs.check(tag, name, req.x, b, req.converged, req.relres,
                         tol)
        log(f"[served] {tag}: iters={np.atleast_1d(req.iters).tolist()} "
            f"host_relres={worst!r} latency_s={req.latency_s!r}")
        served.append((tag, cache.get(gid), tol, b, req))
    check_bitwise(served)
    cols = sum(req.nrhs for req in done)
    st = engine.stats()
    log(f"[served] {len(done)} requests, {cols} columns: all converged, "
        f"host residual within {RESIDUAL_SLACK} x max(tol, float32 "
        f"floor), bit-equal to direct handle.solve; "
        f"step_compiles={st.step_compiles} buckets={st.buckets} | smoke "
        f"times: drain_s={served_s!r} peak_bytes_in_use={peak_bytes(dev)}")


def phase_four_chips(gs: Graphs, devs, rng, n_requests: int) -> None:
    """Three solve replicas + one factor replica, one per chip, against
    a one-replica run of the same trace."""
    import jax
    from repro.serve import SolveCluster
    # skewed: the first graph takes ~3/4 of the traffic; every third
    # request asks for the spai family, so the placements spread over
    # all three solve replicas
    trace = []
    for i in range(n_requests):
        name = GRAPHS[0] if rng.random() < 0.75 else GRAPHS[1]
        spai = i % 3 == 2
        gid = f"{name}::spai" if spai else name
        tol = SERVED_SPAI_TOL if spai else (TOL, SERVED_LOOSE_TOL)[i % 2]
        nrhs = int(rng.integers(1, 5))
        trace.append((name, gid, tol,
                      rhs(rng, gs.g[name].n, 0 if nrhs == 1 else nrhs)))

    def run(replicas: int, factor_replicas: int, devices):
        cl = SolveCluster(replicas=replicas, factor_replicas=factor_replicas,
                          routing="affinity", slots=SLOTS, iters_per_tick=8,
                          devices=devices, max_queue=4 * n_requests,
                          overload="block", cache_kw=CACHE_KW)
        try:
            for name, g in gs.g.items():
                cl.register(g, jax.random.key(SEED), graph_id=name)
            t0 = time.perf_counter()
            futs = [cl.submit(gid, b, tol=tol, maxiter=MAXITER)
                    for _, gid, tol, b in trace]
            done = [fut.result() for fut in futs]
            wall = time.perf_counter() - t0
            check(cl.drain(timeout=600), "four-chips: drain timed out")
            served = []
            for (name, gid, tol, b), req in zip(trace, done):
                tag = (f"four-chips rid={req.rid} {gid} tol={tol} "
                       f"replica={req.replica}")
                check(req.status == "converged",
                      f"{tag}: status {req.status!r}")
                gs.check(tag, name, req.x, b, req.converged, req.relres,
                         tol)
                rep = cl.replicas[req.replica]
                served.append((tag, rep.cache.get(req.graph_id), tol, b,
                               req))
            check_bitwise(served)
            return cl.stats(), done, wall
        finally:
            cl.close(drain=False)

    st, done, wall = run(3, 1, devs[:4])
    check(st.submitted == st.routed + st.shed and st.shed == 0,
          f"four-chips: counts not conserved: submitted={st.submitted} "
          f"routed={st.routed} shed={st.shed}")
    for rs in st.per_replica:
        want = str(devs[rs.index])
        check(rs.device == want, f"replica {rs.index} pinned to "
              f"{rs.device}, expected {want}")
        bydev = rs.cache["fleet_device_bytes_by_device"]
        check(set(bydev) == {want} and all(v > 0 for v in bydev.values()),
              f"replica {rs.index}: fleet bytes {bydev} not on {want}")
        log(f"[four-chips] replica {rs.index} on {want}: routed="
            f"{rs.routed} fleet_device_bytes_by_device={bydev}")
    worker = st.factor_tier["per_replica"][0]
    check(worker["device"] == str(devs[3]),
          f"factor worker on {worker['device']}, expected {devs[3]}")
    log(f"[four-chips] factor worker on {worker['device']}: "
        f"factored={worker['factored']} adoptions={st.adoptions}")
    log(f"[four-chips] {len(done)} requests: all converged, host residual "
        f"within bound, bit-equal to a direct solve on the serving "
        f"replica; submitted={st.submitted} routed={st.routed} "
        f"shed={st.shed} affinity_hits={st.affinity_hits} | smoke times: "
        f"replay_s={wall!r}")

    st1, done1, wall1 = run(1, 0, devs[:1])
    check(st1.submitted == st1.routed + st1.shed and st1.shed == 0,
          "one-replica: counts not conserved")
    same = sum(np.array_equal(np.asarray(a.x), np.asarray(b.x))
               for a, b in zip(done, done1))
    log(f"[four-chips] one-replica run of the same trace: all converged "
        f"and bit-equal to direct solves; {same}/{len(done)} results "
        f"bit-equal to the four-chip run | smoke times: replay_s="
        f"{wall1!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip SolveCluster path")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests in the served (default 8) or "
                         "four-chip (default 24) trace")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: FAIL: no repository sources at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    faulthandler.dump_traceback_later(STACKS_AFTER_S)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: FAIL: no TPU found (JAX sees "
              f"{len(devs)} {devs[0].platform} device(s))", file=sys.stderr)
        return 2
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: FAIL: --four-chips needs 4 TPUs, found "
              f"{len(devs)}", file=sys.stderr)
        return 2

    from repro.kernels import runtime
    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()
    try:
        check(not runtime.default_interpret(), "Pallas kernels resolve to "
              "interpret mode on a TPU (REPRO_PALLAS_INTERPRET is set)")
        log(f"jax {jax.__version__}; devices {devs}; device_kind "
            f"{devs[0].device_kind!r}; compile cache {cache_dir}")
        log(f"fleet SpMV: {spmv_implementation(devs[0])}")
        t0 = time.perf_counter()
        gs = Graphs(GRAPHS)
        log(f"graphs + float64 host references in "
            f"{time.perf_counter() - t0!r} s: " + ", ".join(
                f"{k} n={g.n} m={g.m} float32_floor={gs.floor[k]!r}"
                for k, g in gs.g.items()))
        rng = np.random.default_rng(SEED)
        if args.four_chips:
            phase_four_chips(gs, devs, rng, args.requests or 24)
        else:
            from repro.core.solver import FactorCache
            cache = FactorCache(**CACHE_KW)
            phase_single(cache, gs, devs[0], rng)
            phase_served(cache, gs, devs[0], rng, args.requests or 8)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    faulthandler.cancel_dump_traceback_later()
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

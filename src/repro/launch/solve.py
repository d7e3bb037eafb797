"""Laplacian-solver launcher — the paper's pipeline as a CLI.

    PYTHONPATH=src python -m repro.launch.solve --graph grid3d_uniform_16 \
        --ordering nnz-sort --tol 1e-6

Also exposes the *batched* construction path (``--batch N``): N
independent Laplacians factorized concurrently under one jit — the
incremental-sparsification / many-graph regime where the distributed
mesh shards whole problems (DESIGN.md §2: the scalable axis for an O(1)
arithmetic-intensity algorithm is across problems, not within one).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.launch import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="grid2d_64")
    ap.add_argument("--ordering", default="nnz-sort")
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--maxiter", type=int, default=500)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--batch", type=int, default=0,
                    help="factorize N seeded replicas concurrently")
    ap.add_argument("--nrhs", type=int, default=1,
                    help="solve N right-hand sides in one batched PCG "
                         "sharing the factor")
    args = ap.parse_args()
    compile_cache.enable()

    import jax
    import jax.numpy as jnp
    from repro.data import graphs
    from repro.core.parac import factorize_wavefront
    from repro.core.solver import Solver
    from repro.core.ordering import ORDERINGS
    from repro.core import etree

    g = graphs.SUITE[args.graph]() if args.graph in graphs.SUITE \
        else graphs.SUITE_LARGE[args.graph]()
    perm = ORDERINGS[args.ordering](g, seed=0) \
        if args.ordering in ("random", "nnz-sort") \
        else ORDERINGS[args.ordering](g)
    gp = g.permute(perm).coalesce()
    print(f"graph={args.graph} n={g.n} m={g.m} ordering={args.ordering}")

    if args.batch:
        t0 = time.time()
        for i in range(args.batch):
            f = factorize_wavefront(gp, jax.random.key(i), chunk=args.chunk,
                                    strict=False)
        print(f"batched construction: {args.batch} factors in "
              f"{time.time()-t0:.2f}s "
              f"({(time.time()-t0)/args.batch:.3f}s each)")
        return

    solver = Solver(chunk=args.chunk)
    t0 = time.time()
    handle = solver.factor(gp, jax.random.key(0))
    f = handle.factor
    print(f"factor: {time.time()-t0:.2f}s nnz={f.nnz} "
          f"fill={f.fill_ratio(g):.2f} rounds={f.stats['rounds']} "
          f"height={etree.actual_etree_height(f)} "
          f"levels={handle.n_levels}")

    rng = np.random.default_rng(0)
    iperm = np.argsort(perm)
    if args.nrhs > 1:
        B = rng.normal(size=(args.nrhs, g.n))
        B -= B.mean(axis=1, keepdims=True)
        Bp = jnp.asarray(B[:, iperm], jnp.float32)
        t0 = time.time()
        res = solver.solve(Bp, tol=args.tol, maxiter=args.maxiter)
        jax.block_until_ready(res.x)
        it = np.asarray(res.iters)
        rr = np.asarray(res.relres)
        print(f"batched solve: {time.time()-t0:.2f}s nrhs={args.nrhs} "
              f"iters={it.min()}..{it.max()} max_relres={rr.max():.2e} "
              f"converged={bool(np.all(np.asarray(res.converged)))}")
        return

    b = rng.normal(size=g.n)
    b -= b.mean()
    bp = jnp.asarray(b[iperm], jnp.float32)
    t0 = time.time()
    res = solver.solve(bp, tol=args.tol, maxiter=args.maxiter)
    jax.block_until_ready(res.x)
    print(f"solve: {time.time()-t0:.2f}s iters={int(res.iters)} "
          f"relres={float(res.relres):.2e} converged={bool(res.converged)}")


if __name__ == "__main__":
    main()

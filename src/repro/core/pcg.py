"""Preconditioned conjugate gradient — JAX (jit, production) and numpy
(host, baseline comparisons).

Laplacian systems are singular with nullspace span(1); both solvers keep
iterates mean-zero (standard projection, same as the paper's experimental
setup which reports relative residuals on Laplacian systems).  The JAX
solvers also re-project every updated residual: in float32 the rounding
of ``L·p`` leaves a constant residual component that the factor's
preconditioner amplifies, and on the SUITE_LARGE 2D grid it held the
recurrence residual at 1–2e-6.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from .laplacian import Graph, laplacian_matvec, laplacian_matvec_np


class PCGResult(NamedTuple):
    x: jnp.ndarray
    iters: jnp.ndarray
    relres: jnp.ndarray
    converged: jnp.ndarray


class PCGBatchState(NamedTuple):
    """Carry of the batched PCG loop — exposed so a serving engine can
    drive solves incrementally (``pcg_batched_init`` → repeated
    ``pcg_batched_step``) instead of one closed ``while_loop``.  Lanes
    are independent (frozen-column masking), so a lane's trajectory does
    not depend on which other lanes share the batch or on how the
    iterations are sliced into steps."""

    X: jnp.ndarray        # (nrhs, n) iterate
    R: jnp.ndarray        # (nrhs, n) residual
    Z: jnp.ndarray        # (nrhs, n) preconditioned residual
    P: jnp.ndarray        # (nrhs, n) search direction
    rz: jnp.ndarray       # (nrhs,)
    it: jnp.ndarray       # int32 (nrhs,)
    active: jnp.ndarray   # bool  (nrhs,)
    bnorm: jnp.ndarray    # (nrhs,) — rhs norms (1.0 for zero rhs)


def pcg_jax(matvec: Callable, precond: Callable, b: jnp.ndarray, *,
            tol: float = 1e-6, maxiter: int = 1000,
            project: bool = True) -> PCGResult:
    """Standard PCG; runs under jit (while_loop)."""
    if project:
        b = b - jnp.mean(b)
    bnorm = jnp.linalg.norm(b)
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)

    x0 = jnp.zeros_like(b)
    r0 = b
    z0 = precond(r0)
    if project:
        z0 = z0 - jnp.mean(z0)
    p0 = z0
    rz0 = jnp.vdot(r0, z0)

    def cond(c):
        x, r, z, p, rz, it = c
        return (jnp.linalg.norm(r) / bnorm > tol) & (it < maxiter)

    def body(c):
        x, r, z, p, rz, it = c
        Ap = matvec(p)
        alpha = rz / jnp.vdot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        if project:
            r = r - jnp.mean(r)
        z = precond(r)
        if project:
            z = z - jnp.mean(z)
        rz_new = jnp.vdot(r, z)
        beta = rz_new / rz
        p = z + beta * p
        return (x, r, z, p, rz_new, it + 1)

    x, r, z, p, rz, it = jax.lax.while_loop(
        cond, body, (x0, r0, z0, p0, rz0, jnp.int32(0)))
    relres = jnp.linalg.norm(r) / bnorm
    return PCGResult(x=x, iters=it, relres=relres, converged=relres <= tol)


def pcg_batched_init(matvec: Callable, precond: Callable, B: jnp.ndarray, *,
                     tol=1e-6, project: bool = True) -> PCGBatchState:
    """Set up the batched PCG carry for ``B`` of shape ``(nrhs, n)``.
    ``tol`` may be a scalar or a per-lane ``(nrhs,)`` array (mixed-tol
    continuous batching)."""
    if project:
        B = B - jnp.mean(B, axis=1, keepdims=True)
    bnorm = jnp.linalg.norm(B, axis=1)
    bnorm = jnp.where(bnorm > 0, bnorm, 1.0)
    nrhs = B.shape[0]

    R0 = B
    Z0 = precond(R0)
    if project:
        Z0 = Z0 - jnp.mean(Z0, axis=1, keepdims=True)
    rz0 = jnp.sum(R0 * Z0, axis=1)
    act0 = (jnp.linalg.norm(B, axis=1) / bnorm) > tol
    return PCGBatchState(X=jnp.zeros_like(B), R=R0, Z=Z0, P=Z0, rz=rz0,
                         it=jnp.zeros(nrhs, jnp.int32), active=act0,
                         bnorm=bnorm)


def _pcg_batched_body(matvec: Callable, precond: Callable, *, tol, maxiter,
                      project: bool):
    """One frozen-column batched PCG iteration as a pure
    ``PCGBatchState -> PCGBatchState`` closure — shared by the one-shot
    ``pcg_jax_batched`` loop and the serving engine's incremental
    ``pcg_batched_step``.  ``tol``/``maxiter`` may be scalars or per-lane
    arrays."""
    def _proj(Z):
        return Z - jnp.mean(Z, axis=1, keepdims=True) if project else Z

    def body(s: PCGBatchState) -> PCGBatchState:
        X, R, Z, P, rz, it, active = (s.X, s.R, s.Z, s.P, s.rz, s.it,
                                      s.active)
        AP = matvec(P)
        pAp = jnp.sum(P * AP, axis=1)
        alpha = jnp.where(active, rz / jnp.where(pAp != 0, pAp, 1.0), 0.0)
        Xn = X + alpha[:, None] * P
        Rn = _proj(R - alpha[:, None] * AP)
        Zn = _proj(precond(Rn))
        rz_new = jnp.sum(Rn * Zn, axis=1)
        beta = jnp.where(active, rz_new / jnp.where(rz != 0, rz, 1.0), 0.0)
        Pn = Zn + beta[:, None] * P
        m = active[:, None]
        X = jnp.where(m, Xn, X)
        R = jnp.where(m, Rn, R)
        Z = jnp.where(m, Zn, Z)
        P = jnp.where(m, Pn, P)
        rz = jnp.where(active, rz_new, rz)
        it = it + active.astype(jnp.int32)
        relres = jnp.linalg.norm(R, axis=1) / s.bnorm
        active = active & (relres > tol) & (it < maxiter)
        return PCGBatchState(X=X, R=R, Z=Z, P=P, rz=rz, it=it,
                             active=active, bnorm=s.bnorm)

    return body


def pcg_batched_step(matvec: Callable, precond: Callable,
                     state: PCGBatchState, *, k: int, tol, maxiter,
                     project: bool = True) -> PCGBatchState:
    """Advance every active lane by up to ``k`` PCG iterations (early
    exit when all lanes freeze).  Slicing a solve into steps is exact:
    step-k-then-continue takes the same per-lane iterates as one closed
    loop."""
    body = _pcg_batched_body(matvec, precond, tol=tol, maxiter=maxiter,
                             project=project)

    def cond(c):
        s, j = c
        return jnp.any(s.active) & (j < k)

    def stepped(c):
        s, j = c
        return body(s), j + 1

    state, _ = jax.lax.while_loop(cond, stepped, (state, jnp.int32(0)))
    return state


def pcg_batched_result(state: PCGBatchState, tol) -> PCGResult:
    """Read a ``PCGResult`` off the current carry."""
    relres = jnp.linalg.norm(state.R, axis=1) / state.bnorm
    return PCGResult(x=state.X, iters=state.it, relres=relres,
                     converged=relres <= tol)


def pcg_jax_batched(matvec: Callable, precond: Callable, B: jnp.ndarray, *,
                    tol: float = 1e-6, maxiter: int = 1000,
                    project: bool = True) -> PCGResult:
    """Batched multi-RHS PCG: one ``while_loop`` drives every column of
    ``B`` (shape ``(nrhs, n)``) against the same operator/preconditioner.

    ``matvec``/``precond`` take and return ``(nrhs, n)`` blocks (vmap a
    single-vector closure, or pass a block closure that fuses the rhs
    axis, e.g. the multi-rhs ELL trisolve).  Converged columns are frozen
    by an active mask, so each column takes exactly the iterates of its
    independent single-rhs solve — results match ``pcg_jax`` per column
    instead of drifting while slow columns finish.
    """
    state = pcg_batched_init(matvec, precond, B, tol=tol, project=project)
    body = _pcg_batched_body(matvec, precond, tol=tol, maxiter=maxiter,
                             project=project)
    state = jax.lax.while_loop(lambda s: jnp.any(s.active), body, state)
    return pcg_batched_result(state, tol)


# ---------------------------------------------------------------------------
# Fleet PCG — factor data as traced arguments (shape-bucket mega-batching)
# ---------------------------------------------------------------------------

class FleetArrays(NamedTuple):
    """Stacked, bucket-padded device factors — the **traced** factor
    argument of the fleet PCG programs.  Row ``f`` holds one factor's
    padded Laplacian edge lists, forward/backward trisolve panels (in
    the order of their sweep plans), inverse diagonal and true size; a
    lane gathers its factor by index, so every factor whose padded
    shapes match shares one compiled step program (the factor is data, not a closure constant)."""

    src: jnp.ndarray      # int32[F, m_pad] — Laplacian edges (0-padded)
    dst: jnp.ndarray      # int32[F, m_pad]
    w: jnp.ndarray        # f32[F, m_pad]   (0 on padding)
    fcols: jnp.ndarray    # int32[F, n_pad, Kf] — fwd panels, in the order
    fvals: jnp.ndarray    # f32[F, n_pad, Kf]     of forder (spmv: by row)
    flevel: jnp.ndarray   # int32[F, n_pad]
    bcols: jnp.ndarray    # int32[F, n_pad, Kb] — bwd panels (unflipped),
    bvals: jnp.ndarray    # f32[F, n_pad, Kb]     in the order of border
    blevel: jnp.ndarray   # int32[F, n_pad]
    dinv: jnp.ndarray     # f32[F, n_pad]  — 1/D (0 where D <= 0 / phantom)
    nvalid: jnp.ndarray   # int32[F]       — true vertex count per factor
    fnlv: jnp.ndarray     # int32[F]       — true fwd level count per factor
    bnlv: jnp.ndarray     # int32[F]       — true bwd level count per factor
    # sweep plans of the fwd/bwd panels (``kernels.ops.sweep_plan``);
    # the level pointers span the bucket-wide level bound
    forder: jnp.ndarray   # int32[F, n_pad]
    fext: jnp.ndarray     # int32[F, n_pad]
    fgend: jnp.ndarray    # int32[F, n_pad]
    fptr: jnp.ndarray     # int32[F, f_levels + 1]
    border: jnp.ndarray   # int32[F, n_pad]
    bext: jnp.ndarray     # int32[F, n_pad]
    bgend: jnp.ndarray    # int32[F, n_pad]
    bptr: jnp.ndarray     # int32[F, b_levels + 1]


class FleetPCGState(NamedTuple):
    """Carry of the fleet PCG loop: per-lane iterate block plus the
    per-lane routing/termination scalars.  Everything a serving engine
    needs between ticks lives here, device-resident — admission scatters
    new columns in, retirement gathers finished columns out, and the
    carry itself never round-trips through the host."""

    X: jnp.ndarray        # (L, n_pad)
    R: jnp.ndarray        # (L, n_pad)
    Z: jnp.ndarray        # (L, n_pad)
    P: jnp.ndarray        # (L, n_pad)
    rz: jnp.ndarray       # (L,)
    it: jnp.ndarray       # int32 (L,)
    active: jnp.ndarray   # bool  (L,)
    bnorm: jnp.ndarray    # (L,)
    fidx: jnp.ndarray     # int32 (L,) — lane's factor row in the fleet
    tol: jnp.ndarray      # f32   (L,)
    maxiter: jnp.ndarray  # int32 (L,)


@jax.named_scope("fleet_matvec")
def fleet_matvec(fa: FleetArrays, fidx: jnp.ndarray,
                 Y: jnp.ndarray) -> jnp.ndarray:
    """Per-lane Laplacian matvec: lane ``l`` multiplies by the operator
    of factor ``fidx[l]`` (edge lists gathered from the fleet stack).
    Zero-weight padding edges contribute exactly zero."""
    src = fa.src[fidx]
    dst = fa.dst[fidx]
    w = fa.w[fidx]

    def one(s, d, ww, y):
        diff = ww * (y[s] - y[d])
        return jnp.zeros_like(y).at[s].add(diff).at[d].add(-diff)

    return jax.vmap(one)(src, dst, w, Y)


def fleet_precondition(fa: FleetArrays, fidx: jnp.ndarray, R: jnp.ndarray,
                       *, f_levels: int, b_levels: int,
                       kind: str = "factor", f_width=None, b_width=None,
                       active=None) -> jnp.ndarray:
    """Per-lane preconditioner apply, dispatched on the **static** apply
    ``kind`` of the family that owns the fleet:

    * ``"factor"`` — ``(G D Gᵀ)⁺`` apply: forward masked trisolve → D⁻¹
      scale → backward masked trisolve, panels gathered per lane.  The
      level bounds are bucket-wide maxima; lanes whose factor has fewer
      levels stop selecting rows early (masked no-op), so over-padding
      the bound never changes a lane's result.  Used by the randomized
      AC factor and the incomplete-Cholesky families.
    * ``"spmv"`` — ``M r``: one lane-batched ELL SpMV of a materialized
      approximate inverse whose rows live in the forward-panel slots
      (``fcols``/``fvals``); the backward panels and ``dinv`` are inert.
      Used by SPAI and the flattened AMG operator — a single kernel
      launch per apply instead of ``f_levels + b_levels`` masked sweeps.

    ``kind`` must be static under jit (it selects the traced program),
    as must ``f_width``/``b_width``: the rows of one trisolve sweep
    (``kernels.ops.trisolve_fleet``'s ``width``, the largest
    ``PackedSchedule.sweep_width`` of any fleet member; ``None`` sweeps
    a whole level at a time).  The sweeps walk the fleet's stored sweep
    plans (``forder``/``fext``/``fgend``/``fptr`` and their backward
    twins).

    The static ``f_levels``/``b_levels`` ceilings bound compilation; the
    *trip count* of each trisolve is further bounded dynamically by the
    batch's live maximum true level count (``fa.fnlv``/``fa.bnlv``
    gathered per lane), so sweeps past every live lane's depth never
    launch.  ``active`` (optional bool ``(L,)``) masks frozen lanes out
    of the bound — their apply output is discarded by the caller's lane
    mask, so shrinking their sweep count cannot change any result.
    """
    # deferred: kernels.ops pulls in kernels.ref → repro.core, so a
    # top-level import here is a cycle whenever kernels.ops loads first
    from repro.kernels.ops import ell_spmv_fleet, trisolve_fleet
    if kind == "spmv":
        return ell_spmv_fleet(fa.fcols[fidx], fa.fvals[fidx], R)
    if kind != "factor":
        raise ValueError(f"unknown preconditioner apply kind: {kind!r}")
    flv = fa.fnlv[fidx]
    blv = fa.bnlv[fidx]
    if active is not None:
        flv = jnp.where(active, flv, 1)
        blv = jnp.where(active, blv, 1)
    Y = trisolve_fleet(fa.fcols, fa.fvals, None, R, fidx=fidx,
                       n_levels=f_levels, lane_levels=flv, width=f_width,
                       plan=(fa.forder[fidx], fa.fext[fidx], fa.fgend[fidx],
                             fa.fptr[fidx, :f_levels + 1]))
    with jax.named_scope("pcg_update"):
        Z = Y * fa.dinv[fidx]
    return trisolve_fleet(fa.bcols, fa.bvals, None, Z, fidx=fidx,
                          n_levels=b_levels, lane_levels=blv,
                          width=b_width,
                          plan=(fa.border[fidx], fa.bext[fidx],
                                fa.bgend[fidx], fa.bptr[fidx, :b_levels + 1]))


@jax.named_scope("pcg_update")
def _fleet_project(Y: jnp.ndarray, nvalid: jnp.ndarray) -> jnp.ndarray:
    """Mean-zero projection restricted to each lane's true vertices.
    Padding entries are forced (back) to exactly 0 so padded reductions
    (norms, dot products) equal their unpadded counterparts."""
    nv = jnp.maximum(nvalid, 1).astype(Y.dtype)
    mean = jnp.sum(Y, axis=1) / nv
    vmask = jnp.arange(Y.shape[1], dtype=jnp.int32)[None, :] \
        < nvalid[:, None]
    return jnp.where(vmask, Y - mean[:, None], 0.0)


def pcg_fleet_init(fa: FleetArrays, fidx, B, tol, maxiter, *,
                   f_levels: int, b_levels: int, kind: str = "factor",
                   f_width=None, b_width=None,
                   project: bool = True) -> FleetPCGState:
    """Set up the fleet PCG carry for columns ``B`` of shape
    ``(L, n_pad)`` (each zero-padded past its factor's true n).  ``tol``
    and ``maxiter`` are per-lane arrays; lane ``l`` solves against
    factor ``fidx[l]``.  ``kind`` is the fleet's static apply kind (see
    :func:`fleet_precondition`)."""
    fidx = jnp.asarray(fidx, jnp.int32)
    with jax.named_scope("pcg_update"):
        nvalid = fa.nvalid[fidx]
        if project:
            B = _fleet_project(B, nvalid)
        bnorm = jnp.linalg.norm(B, axis=1)
        bnorm = jnp.where(bnorm > 0, bnorm, 1.0)
    R0 = B
    Z0 = fleet_precondition(fa, fidx, R0, f_levels=f_levels,
                            b_levels=b_levels, kind=kind, f_width=f_width,
                            b_width=b_width)
    with jax.named_scope("pcg_update"):
        if project:
            Z0 = _fleet_project(Z0, nvalid)
        rz0 = jnp.sum(R0 * Z0, axis=1)
        act0 = (jnp.linalg.norm(B, axis=1) / bnorm) > tol
        L = B.shape[0]
        return FleetPCGState(
            X=jnp.zeros_like(B), R=R0, Z=Z0, P=Z0, rz=rz0,
            it=jnp.zeros(L, jnp.int32), active=act0, bnorm=bnorm,
            fidx=fidx, tol=jnp.asarray(tol, jnp.float32),
            maxiter=jnp.asarray(maxiter, jnp.int32))


def _pcg_fleet_body(fa: FleetArrays, *, f_levels: int, b_levels: int,
                    kind: str = "factor", f_width=None, b_width=None,
                    project: bool):
    """One frozen-lane fleet PCG iteration as a pure
    ``FleetPCGState -> FleetPCGState`` closure over the **traced** fleet
    arrays — the factor-as-data restatement of ``_pcg_batched_body``.
    Lane independence is preserved: a lane's update reads only its own
    row and its own factor's fleet rows, so trajectories do not depend
    on batch composition, padding lanes, or step slicing."""
    def body(s: FleetPCGState) -> FleetPCGState:
        AP = fleet_matvec(fa, s.fidx, s.P)
        with jax.named_scope("pcg_update"):
            nvalid = fa.nvalid[s.fidx]
            pAp = jnp.sum(s.P * AP, axis=1)
            alpha = jnp.where(s.active,
                              s.rz / jnp.where(pAp != 0, pAp, 1.0), 0.0)
            Xn = s.X + alpha[:, None] * s.P
            Rn = s.R - alpha[:, None] * AP
        if project:
            # L·P is mean-zero only up to rounding, and the factor's
            # preconditioner amplifies a constant residual component:
            # left in, it stalls the float32 recurrence near 1e-6
            Rn = _fleet_project(Rn, nvalid)
        Zn = fleet_precondition(fa, s.fidx, Rn, f_levels=f_levels,
                                b_levels=b_levels, kind=kind,
                                f_width=f_width, b_width=b_width,
                                active=s.active)
        if project:
            Zn = _fleet_project(Zn, nvalid)
        with jax.named_scope("pcg_update"):
            rz_new = jnp.sum(Rn * Zn, axis=1)
            beta = jnp.where(s.active,
                             rz_new / jnp.where(s.rz != 0, s.rz, 1.0), 0.0)
            Pn = Zn + beta[:, None] * s.P
            m = s.active[:, None]
            X = jnp.where(m, Xn, s.X)
            R = jnp.where(m, Rn, s.R)
            Z = jnp.where(m, Zn, s.Z)
            P = jnp.where(m, Pn, s.P)
            rz = jnp.where(s.active, rz_new, s.rz)
            it = s.it + s.active.astype(jnp.int32)
            relres = jnp.linalg.norm(R, axis=1) / s.bnorm
            active = s.active & (relres > s.tol) & (it < s.maxiter)
            return FleetPCGState(X=X, R=R, Z=Z, P=P, rz=rz, it=it,
                                 active=active, bnorm=s.bnorm, fidx=s.fidx,
                                 tol=s.tol, maxiter=s.maxiter)

    return body


def pcg_fleet_step(fa: FleetArrays, state: FleetPCGState, *, k: int,
                   f_levels: int, b_levels: int, kind: str = "factor",
                   f_width=None, b_width=None,
                   project: bool = True) -> FleetPCGState:
    """Advance every active lane by up to ``k`` iterations (early exit
    when all lanes freeze).  Step slicing is exact, as in
    ``pcg_batched_step``."""
    body = _pcg_fleet_body(fa, f_levels=f_levels, b_levels=b_levels,
                           kind=kind, f_width=f_width, b_width=b_width,
                           project=project)

    def cond(c):
        s, j = c
        return jnp.any(s.active) & (j < k)

    def stepped(c):
        s, j = c
        return body(s), j + 1

    state, _ = jax.lax.while_loop(cond, stepped, (state, jnp.int32(0)))
    return state


def pcg_fleet_solve(fa: FleetArrays, fidx, B, tol, maxiter, *,
                    f_levels: int, b_levels: int, kind: str = "factor",
                    f_width=None, b_width=None,
                    project: bool = True) -> FleetPCGState:
    """One-shot fleet solve: init then iterate until every lane freezes.
    Runs the same body as ``pcg_fleet_step``, so an engine slicing the
    same solve into ticks takes bit-identical per-lane iterates."""
    state = pcg_fleet_init(fa, fidx, B, tol, maxiter, f_levels=f_levels,
                           b_levels=b_levels, kind=kind, f_width=f_width,
                           b_width=b_width, project=project)
    body = _pcg_fleet_body(fa, f_levels=f_levels, b_levels=b_levels,
                           kind=kind, f_width=f_width, b_width=b_width,
                           project=project)
    return jax.lax.while_loop(lambda s: jnp.any(s.active), body, state)


def pcg_fleet_result(state: FleetPCGState, n: int) -> PCGResult:
    """Read a ``PCGResult`` off the fleet carry, sliced to true size."""
    relres = jnp.linalg.norm(state.R, axis=1) / state.bnorm
    return PCGResult(x=state.X[:, :n], iters=state.it, relres=relres,
                     converged=relres <= state.tol)


def pcg_np(matvec: Callable, precond: Callable, b: np.ndarray, *,
           tol: float = 1e-6, maxiter: int = 1000,
           project: bool = True) -> PCGResult:
    """Host PCG for baseline preconditioners (ichol, Jacobi, AMG)."""
    b = np.asarray(b, np.float64)
    if project:
        b = b - b.mean()
    bnorm = np.linalg.norm(b) or 1.0
    x = np.zeros_like(b)
    r = b.copy()
    z = np.asarray(precond(r), np.float64)
    if project:
        z = z - z.mean()
    p = z.copy()
    rz = float(r @ z)
    it = 0
    relres = np.linalg.norm(r) / bnorm
    while relres > tol and it < maxiter:
        Ap = np.asarray(matvec(p), np.float64)
        alpha = rz / float(p @ Ap)
        x += alpha * p
        r -= alpha * Ap
        z = np.asarray(precond(r), np.float64)
        if project:
            z = z - z.mean()
        rz_new = float(r @ z)
        beta = rz_new / rz
        p = z + beta * p
        rz = rz_new
        it += 1
        relres = np.linalg.norm(r) / bnorm
    return PCGResult(x=x, iters=np.int32(it), relres=np.float64(relres),
                     converged=relres <= tol)


def laplacian_pcg_jax(g: Graph, precond: Callable, b: jnp.ndarray,
                      **kw) -> PCGResult:
    src = jnp.asarray(g.src)
    dst = jnp.asarray(g.dst)
    w = jnp.asarray(g.w, dtype=b.dtype)
    mv = partial(laplacian_matvec, src, dst, w, g.n)
    return pcg_jax(mv, precond, b, **kw)


def laplacian_pcg_jax_batched(g: Graph, precond: Callable, B: jnp.ndarray,
                              **kw) -> PCGResult:
    """Batched Laplacian PCG; ``precond`` takes an ``(nrhs, n)`` block."""
    src = jnp.asarray(g.src)
    dst = jnp.asarray(g.dst)
    w = jnp.asarray(g.w, dtype=B.dtype)
    mv = jax.vmap(partial(laplacian_matvec, src, dst, w, g.n))
    return pcg_jax_batched(mv, precond, B, **kw)


def laplacian_pcg_np(g: Graph, precond: Callable, b: np.ndarray,
                     **kw) -> PCGResult:
    return pcg_np(lambda x: laplacian_matvec_np(g, x), precond, b, **kw)

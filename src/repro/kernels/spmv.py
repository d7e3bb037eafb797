"""Pallas ELL-format SpMV kernels (paper §6: both the randomized factor
application and CG's matvec are bandwidth-bound; ELL padding makes the
access pattern rectangular).

Layout: rows padded to a fixed ``K`` nonzeros (ELLPACK).  Each grid step
processes a ``(Rb, K)`` row tile: gather x at the tile's column
indices, multiply by the tile's values, reduce along K.  The x block
spans the whole vector, and the gather is a general random gather out
of it.

Only interpret mode runs these kernels: the TPU compiler refuses the
random gather (``Only 2D gather is supported``), and the fleet kernel's
``(1, n)`` block breaks its (8, 128) tiling rule.  The served path
therefore uses the XLA form ``repro.kernels.ops.ell_spmv_fleet``, which
computes the same product; ``tests/test_tpu_compile.py`` compiles it
for a v5e.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def _spmv_kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[...]                 # (Rb, K) int32, padded with 0
    vals = vals_ref[...]                 # (Rb, K) f32, padded with 0.0
    x = x_ref[...]                       # (n,) f32 — whole vector in VMEM
    contrib = vals * x[cols]
    y_ref[...] = jnp.sum(contrib, axis=1, keepdims=True)


def _pick_block_rows(R: int, block_rows: int) -> int:
    """Largest divisor of R that is ≤ block_rows (grid must tile R),
    preferring sublane multiples of 8 so fp32 row tiles land on the
    (8, 128) TPU tile grid.  Power-of-two ``R`` (the fleet's bucket
    shapes) picks the same value either way; ragged ``R`` only falls
    back to a non-multiple-of-8 divisor when no aligned one exists."""
    cap = max(1, min(block_rows, R))
    aligned = cap - cap % 8
    while aligned >= 8:
        if R % aligned == 0:
            return aligned
        aligned -= 8
    Rb = cap
    while R % Rb:
        Rb -= 1
    return Rb


def ell_spmv_pallas(cols, vals, x, *, block_rows: int = 256,
                    interpret: Optional[bool] = None):
    """y[i] = Σ_k vals[i,k] · x[cols[i,k]].  cols/vals: [R, K]; x: [n]."""
    interpret = resolve_interpret(interpret)
    R, K = cols.shape
    n = x.shape[0]
    Rb = _pick_block_rows(R, block_rows)
    grid = (R // Rb,)
    return pl.pallas_call(
        _spmv_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((Rb, K), lambda r: (r, 0)),
                  pl.BlockSpec((Rb, K), lambda r: (r, 0)),
                  pl.BlockSpec((n,), lambda r: (0,))],
        out_specs=pl.BlockSpec((Rb, 1), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), vals.dtype),
        interpret=interpret,
    )(cols, vals, x)[:, 0]


def _spmv_fleet_kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[0]                   # (Rb, K) int32 — one lane's tile
    vals = vals_ref[0]                   # (Rb, K) f32
    x = x_ref[0]                         # (n,) f32 — the lane's own vector
    contrib = vals * x[cols]
    y_ref[0, :] = jnp.sum(contrib, axis=1)


def ell_spmv_fleet_pallas(cols, vals, x, *, block_rows: int = 256,
                          interpret: Optional[bool] = None):
    """Lane-batched ELL SpMV: Y[l, i] = Σ_k vals[l,i,k] · x[l, cols[l,i,k]].

    cols/vals: [L, R, K]; x: [L, n].  Every lane carries its *own* panel
    arrays — the shape-bucket mega-batching formulation, where panels are
    gathered per lane from a stacked fleet of factors and passed as traced
    arguments (no per-factor closure constants, so one compiled program
    serves every factor in the bucket).  The grid walks (lane, row-tile);
    each step gathers the lane's x at the tile's column indices,
    multiplies by the tile's values and reduces along K — identical
    per-tile math to ``ell_spmv_pallas``, so a lane's result does not
    depend on how many lanes share the batch.
    """
    interpret = resolve_interpret(interpret)
    L, R, K = cols.shape
    n = x.shape[1]
    Rb = _pick_block_rows(R, block_rows)
    grid = (L, R // Rb)
    return pl.pallas_call(
        _spmv_fleet_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, Rb, K), lambda l, r: (l, r, 0)),
                  pl.BlockSpec((1, Rb, K), lambda l, r: (l, r, 0)),
                  pl.BlockSpec((1, n), lambda l, r: (l, 0))],
        out_specs=pl.BlockSpec((1, Rb), lambda l, r: (l, r)),
        out_shape=jax.ShapeDtypeStruct((L, R), vals.dtype),
        interpret=interpret,
    )(cols, vals, x)


def _spmv_multi_kernel(cols_ref, vals_ref, x_ref, y_ref):
    cols = cols_ref[...]                 # (Rb, K) int32, padded with 0
    vals = vals_ref[...]                 # (Rb, K) f32, padded with 0.0
    x = x_ref[...]                       # (n, B) f32 — rhs block in VMEM
    contrib = vals[:, :, None] * x[cols]         # (Rb, K, B)
    y_ref[...] = jnp.sum(contrib, axis=1)


def ell_spmv_multi_pallas(cols, vals, x, *, block_rows: int = 256,
                          interpret: Optional[bool] = None):
    """Multi-rhs ELL SpMV: Y[i, b] = Σ_k vals[i,k] · x[cols[i,k], b].

    cols/vals: [R, K]; x: [n, B].  One kernel pass serves the whole rhs
    block — the solve-phase shape of the Solver's batched PCG, where the
    factor (and its level panels) are shared across B simultaneous
    systems.  Bandwidth per row is amortized: the (Rb, K) index/value
    tiles are read once for all B columns.
    """
    interpret = resolve_interpret(interpret)
    R, K = cols.shape
    n, B = x.shape
    Rb = _pick_block_rows(R, block_rows)
    grid = (R // Rb,)
    return pl.pallas_call(
        _spmv_multi_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((Rb, K), lambda r: (r, 0)),
                  pl.BlockSpec((Rb, K), lambda r: (r, 0)),
                  pl.BlockSpec((n, B), lambda r: (0, 0))],
        out_specs=pl.BlockSpec((Rb, B), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, B), vals.dtype),
        interpret=interpret,
    )(cols, vals, x)

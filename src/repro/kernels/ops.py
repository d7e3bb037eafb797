"""Jit'd public wrappers around the kernels: padding, layout
conversion, and level-scheduled triangular solve built on the SpMV.

The served path's lane-batched SpMV (``ell_spmv_fleet``, and the masked
sweeps of ``trisolve_fleet`` built on it) is plain XLA on every backend.
The Pallas wrappers (``sample_clique``, ``ell_spmv``, ``ell_spmv_multi``
and the single-factor solves on them) take ``interpret=None``: the mode
is resolved per process by :mod:`repro.kernels.runtime`
(``REPRO_PALLAS_INTERPRET`` env override, else interpret on CPU and
native on GPU/TPU backends).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .sample_clique import sample_clique_pallas, INVALID_ID
from .runtime import resolve_interpret
from .spmv import ell_spmv_pallas, ell_spmv_multi_pallas
from . import ref as kref


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


@partial(jax.jit, static_argnames=("interpret", "block_rows"))
def sample_clique(ids, ws, fill, u, *, interpret: Optional[bool] = None,
                  block_rows: int = 8):
    """Batched vertex elimination.  ids/ws/u: [R, W]; fill: [R].
    Pads W to a power of two and dispatches to the Pallas kernel."""
    interpret = resolve_interpret(interpret)
    R, W = ids.shape
    W2 = max(_next_pow2(W), 2)
    if W2 != W:
        pad = ((0, 0), (0, W2 - W))
        ids = jnp.pad(ids, pad, constant_values=INVALID_ID)
        ws = jnp.pad(ws, pad)
        u = jnp.pad(u, pad, constant_values=0.5)
    return sample_clique_pallas(ids, ws, fill, u, block_rows=block_rows,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def ell_spmv(cols, vals, x, *, interpret: Optional[bool] = None):
    return ell_spmv_pallas(cols, vals, x, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def ell_spmv_multi(cols, vals, x, *, interpret: Optional[bool] = None):
    """Multi-rhs ELL SpMV; x: [n, B] → y: [R, B]."""
    return ell_spmv_multi_pallas(cols, vals, x, interpret=interpret)


def graph_to_ell(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                 n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Laplacian rows in ELL layout (diagonal + negated off-diagonals)."""
    deg = np.zeros(n, np.int64)
    np.add.at(deg, src, 1)
    np.add.at(deg, dst, 1)
    K = int(deg.max()) + 1                       # +1 for the diagonal
    cols = np.zeros((n, K), np.int32)
    vals = np.zeros((n, K), np.float32)
    fill = np.ones(n, np.int64)                  # slot 0 = diagonal
    cols[:, 0] = np.arange(n)
    for s, d, ww in zip(src, dst, w):
        vals[s, 0] += ww
        vals[d, 0] += ww
        cols[s, fill[s]] = d
        vals[s, fill[s]] = -ww
        fill[s] += 1
        cols[d, fill[d]] = s
        vals[d, fill[d]] = -ww
        fill[d] += 1
    return cols, vals


def schedule_to_ell(sched) -> Tuple[np.ndarray, ...]:
    """Pad a trisolve LevelSchedule into per-level ELL rows.

    Returns (row_ids, cols, vals, level_ptr) with rows grouped by level;
    each row padded to the level's max in-degree.  Vectorized: per-level
    packing is a stable sort + rank scatter, no per-edge Python loop.
    """
    rows_all, cols_all, vals_all, ptr = [], [], [], [0]
    for lv in range(sched.n_levels):
        lo, hi = int(sched.level_ptr[lv]), int(sched.level_ptr[lv + 1])
        if hi == lo:
            ptr.append(ptr[-1])
            continue
        dst = sched.e_dst[lo:hi]
        uniq, inv = np.unique(dst, return_inverse=True)
        counts = np.bincount(inv)
        K = int(counts.max())
        # rank of each edge within its dst group (edges already grouped
        # arbitrarily; stable sort by inv gives contiguous groups)
        order = np.argsort(inv, kind="stable")
        starts = np.zeros(uniq.size + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        rank = np.arange(hi - lo) - np.repeat(starts[:-1], counts)
        cols = np.zeros((uniq.size, K), np.int32)
        vals = np.zeros((uniq.size, K), np.float32)
        cols[inv[order], rank] = sched.e_src[lo:hi][order]
        vals[inv[order], rank] = sched.e_val[lo:hi][order]
        rows_all.append(uniq.astype(np.int32))
        cols_all.append(cols)
        vals_all.append(vals)
        ptr.append(ptr[-1] + uniq.size)
    return rows_all, cols_all, vals_all, np.asarray(ptr)


def trisolve_levels(level_rows, level_cols, level_vals, b, flip: bool = False,
                    interpret: Optional[bool] = None):
    """Level-scheduled unit-triangular solve driven by the SpMV kernel."""
    y = jnp.asarray(b[::-1] if flip else b)
    for rows, cols, vals in zip(level_rows, level_cols, level_vals):
        rows = jnp.asarray(rows)
        upd = y[rows] - ell_spmv(jnp.asarray(cols), jnp.asarray(vals), y,
                                 interpret=interpret)
        y = y.at[rows].set(upd)
    return y[::-1] if flip else y


@jax.jit
@jax.named_scope("ell_spmv_fleet")
def ell_spmv_fleet(cols, vals, x):
    """Lane-batched ELL SpMV; cols/vals: [L, R, K], x: [L, n] → [L, R].

    An XLA gather-multiply-reduce on every backend: ``Y[l, i] =
    Σ_k vals[l,i,k] · x[l, cols[l,i,k]]``.  With cols/vals ``[R, K]``
    every lane multiplies by the same rows (``vals[i,k] · x[l,
    cols[i,k]]``), and one index fetches all ``L`` lanes' values.  The
    served path (every fleet PCG apply and masked sweep) runs this form,
    so CPU tests exercise the program the TPU runs.
    ``ell_spmv_fleet_pallas`` computes the same product as a Pallas
    kernel; the TPU compiler refuses its random gather out of VMEM, so
    it is not on the served path."""
    L = x.shape[0]
    if cols.ndim == 2:
        R, K = cols.shape
        return _pairwise_sum(vals * x[:, cols.reshape(-1)].reshape(L, R, K))
    _, R, K = cols.shape
    xg = jnp.take_along_axis(x, cols.reshape(L, R * K), axis=1)
    return _pairwise_sum(vals * xg.reshape(L, R, K))


def shares_index(rows: int, lanes: int) -> bool:
    """Whether the lanes of a fleet program can read only one row of its
    factor stack: the stack holds ``rows == 1``, or the program has
    ``lanes == 1``.  Such lanes walk one sweep plan in lockstep, so
    :func:`trisolve_fleet` and ``pcg.fleet_matvec`` read that row once
    and fetch every lane's values with one shared index."""
    return rows == 1 or lanes == 1


def _pairwise_sum(p):
    """Sum over the last axis in a fixed pairwise order.  Elementwise
    adds cannot be reassociated by the compiler, so a row's sum does not
    depend on how many lanes or rows share the array — what keeps a
    served lane bit-identical to a direct solve (``jnp.sum``'s order is
    the backend's choice and changes with the batch shape)."""
    while p.shape[-1] > 1:
        k = p.shape[-1]
        h = k // 2
        head = p[..., :h] + p[..., h:2 * h]
        p = head if k % 2 == 0 else jnp.concatenate([head, p[..., 2 * h:]],
                                                    axis=-1)
    return p[..., 0]


def trisolve_masked(cols, vals, level_of, y, *, n_levels: int,
                    interpret: Optional[bool] = None):
    """Level-masked unit-triangular solve with **traced** panel arguments.

    ``cols``/``vals`` are row-indexed ELL panels ``(n, K)`` (row ``i``'s
    in-edges live in slot ``i``, zero-padded), ``level_of`` the dependency
    level per row, ``y`` the rhs ``(n,)``.  Unlike ``trisolve_panels``,
    nothing here is a closed-over constant or host-sliced slab: the whole
    schedule rides in as arrays, and the only static is the level-loop
    bound — so one compiled program serves every factor whose padded
    shapes (and level bound) match.  Each level runs the full-row SpMV
    and commits only the rows at that level; rows above ``level_of``'s
    true maximum are never selected, so over-padding ``n_levels`` (to a
    bucket-wide bound) does not change the result.
    """
    def body(lv, y):
        contrib = ell_spmv(cols, vals, y, interpret=interpret)
        return jnp.where(level_of == lv, y - contrib, y)

    return jax.lax.fori_loop(1, n_levels, body, y)


def panel_class(extent):
    """A row's panel class: the power of two at or above its panel
    extent, at least 8.  Rows of one class are swept together on that
    many panel slots (``trisolve_fleet``)."""
    e = jnp.maximum(jnp.asarray(extent, jnp.int32), 1)
    return jnp.maximum(jnp.left_shift(1, 32 - jax.lax.clz(e - 1)), 8)


def sweep_plan(vals, level_of, n_levels: int):
    """The row order a fleet trisolve sweeps, computed from panels.

    For lanes of row-indexed panels ``vals`` ``(L, n, K)`` and levels
    ``level_of`` ``(L, n)`` returns ``(order, extent, group_end,
    level_ptr)``: ``order`` lists each lane's rows by ascending level,
    within a level by descending panel extent (one past the row's last
    nonzero slot — slots beyond it contribute exactly zero), then by row
    id; ``extent[l, j]`` is the extent of row ``order[l, j]``;
    ``group_end[l, j]`` is where the run of rows sharing position
    ``j``'s level and :func:`panel_class` ends; ``level_ptr[l, v]`` is
    where level ``v`` starts (``n`` past the last level).  Schedules
    built for a fleet carry the same arrays precomputed
    (``trisolve.PackedSchedule``); this in-program form serves callers
    that hold bare panels."""
    L, n, K = vals.shape
    slot = jnp.arange(1, K + 1, dtype=jnp.int32)
    ext = jnp.max(jnp.where(vals != 0, slot, 0), axis=2)          # (L, n)
    key = level_of.astype(jnp.int32) * (K + 1) + (K - ext)
    order = jnp.argsort(key, axis=1, stable=True).astype(jnp.int32)
    extent = jnp.take_along_axis(ext, order, axis=1)
    lv = jnp.take_along_axis(level_of.astype(jnp.int32), order, axis=1)
    cls = panel_class(extent)
    pos = jnp.arange(n, dtype=jnp.int32)
    last = jnp.concatenate(
        [(lv[:, 1:] != lv[:, :-1]) | (cls[:, 1:] != cls[:, :-1]),
         jnp.ones((L, 1), bool)], axis=1)                  # run ends at j
    group_end = jax.lax.associative_scan(
        jnp.minimum, jnp.where(last, pos + 1, n), axis=1, reverse=True)
    level_ptr = jax.vmap(lambda s: jnp.searchsorted(
        s, jnp.arange(n_levels + 1, dtype=s.dtype), side="left"))(lv)
    return order, extent, group_end.astype(jnp.int32), \
        level_ptr.astype(jnp.int32)


def _k_classes(K: int):
    """The panel classes a sweep of ``K``-slot panels runs: powers of
    two from 8 up to ``K`` when ``K`` is a power of two above 8, else
    ``K`` alone."""
    if K <= 8 or K & (K - 1):
        return (K,)
    classes, k = [], 8
    while k <= K:
        classes.append(k)
        k *= 2
    return tuple(classes)


def trisolve_sweeps(extent, group_end, level_ptr, n_levels: int, K: int,
                    width: int) -> int:
    """The sweeps :func:`trisolve_fleet` makes for one lane through one
    triangular solve, counted on the host from the lane's sweep plan
    (``extent``/``group_end`` in plan order, ``level_ptr`` the level
    starts, ``n_levels`` the factor's true level count) for panels
    ``K`` slots wide and ``width`` rows per sweep of the narrowest
    class — the same walk as the program's loop."""
    extent, group_end = np.asarray(extent), np.asarray(group_end)
    level_ptr = np.asarray(level_ptr)
    n = int(extent.shape[0])
    classes = _k_classes(int(K))
    cls_of = np.maximum(
        1 << np.ceil(np.log2(np.maximum(extent, 1))).astype(np.int64), 8)
    rows_c = max(1, min(int(width), n))
    bound = min(max(int(n_levels), 1), int(level_ptr.shape[0]) - 1)
    start, stop = int(level_ptr[1]), int(level_ptr[bound])
    sweeps = 0
    while start < stop:
        kc = next((k for k in classes[:-1] if k >= cls_of[start]),
                  classes[-1])
        rows_k = max(1, rows_c * classes[0] // kc)
        start = min(start + rows_k, int(group_end[start]))
        sweeps += 1
    return sweeps


@jax.named_scope("trisolve_fleet")
def trisolve_fleet(cols, vals, level_of, y, *, n_levels: int,
                   lane_levels=None, width=None, plan=None, fidx=None):
    """Lane-batched level-scheduled unit-triangular solve: cols/vals
    ``(L, n, K)`` row-indexed panels, ``level_of`` ``(L, n)``, ``y``
    ``(L, n)`` — each lane solves against its own panels.  With
    ``fidx`` (``(L,)`` int32) ``cols``/``vals`` are instead a factor
    fleet's ``(F, n, K)`` stacks and lane ``l`` reads row ``fidx[l]``:
    sweeps gather their rows straight out of the stack, and no lane
    copy of the panels is made.

    Each lane walks its rows in :func:`sweep_plan` order, in sweeps
    that never cross a run of one level and one :func:`panel_class`: a
    level takes as many sweeps as its runs need, and each sweep gathers
    only its class's leading panel slots.  A sweep of the narrowest
    class takes at most ``width`` rows (default ``n``), and a class of
    ``k`` slots proportionally fewer (``width × narrowest / k``, at
    least one), so every sweep gathers about as many slots.  Lanes
    advance on their own; a sweep runs the widest class any live lane
    is at, and lanes at a narrower class wait for it.  So a row's update
    ``y - Σ_k vals·y[cols]`` is computed by the same arithmetic, on the
    same slots, whatever the sweep width or lane mix — these change the
    work (a few sweeps per level, instead of one sweep of ``n × K``
    slots), never a result.

    Where every lane reads one row of the stack (:func:`shares_index`:
    a one-row stack, or one lane) the lanes walk that row's plan in
    lockstep, each stopping at its own level bound.  Each sweep then
    reads its panel block once and fetches all lanes' values with one
    shared index (``y[:, cols]``), and lanes past their bound keep their
    values: the same sweeps and the same sums as the per-lane walk.

    ``plan`` is ``sweep_plan``'s ``(order, extent, group_end,
    level_ptr)`` with ``level_ptr`` ``n_levels + 1`` wide, one row per
    lane (or, where the lanes share a row, one row in all).  With a
    plan, the panels are stored in plan order — panel row ``j`` of a
    lane holds the in-edges of row ``order[j]``, as a fleet stores them
    — so a sweep reads one contiguous block of panel rows (a gather of
    rows by index is a loop of single-row copies on a TPU).  Without
    one, the plan is computed here and the row-indexed panels are put
    into plan order first.  ``n_levels`` is the static bucket-wide
    ceiling; ``lane_levels`` (optional, ``(L,)`` int32, traced) carries
    each lane's *true* level count, and a lane stops there (a lane
    given 1 does nothing) — levels past it select no rows, so stopping
    early only removes no-op sweeps."""
    L, n = y.shape
    K = cols.shape[2]
    C = n if width is None else max(1, min(int(width), n))
    shared = shares_index(cols.shape[0], L)
    lane_of = jnp.arange(L) if fidx is None else fidx
    if shared:
        lane_of = lane_of[:1]             # the one row every lane reads
    if plan is None:
        if fidx is not None:
            cols, vals = cols[lane_of], vals[lane_of]
            level_of = level_of[:cols.shape[0]]
            lane_of = jnp.arange(cols.shape[0])
        plan = sweep_plan(vals, level_of, n_levels)
        cols = jnp.take_along_axis(cols, plan[0][:, :, None], axis=1)
        vals = jnp.take_along_axis(vals, plan[0][:, :, None], axis=1)
    if shared:
        plan = tuple(p[:1] for p in plan)
    order, extent, group_end, level_ptr = plan
    P = order.shape[0]                    # plan rows: L, or 1 if shared
    first = jnp.concatenate(
        [panel_class(extent), jnp.zeros((P, 1), jnp.int32)], axis=1)
    group_end = jnp.concatenate(
        [group_end.astype(jnp.int32), jnp.full((P, 1), n, jnp.int32)],
        axis=1)
    bound = jnp.full((L,), n_levels, jnp.int32) if lane_levels is None \
        else jnp.clip(lane_levels.astype(jnp.int32), 1, n_levels)
    level_ptr = level_ptr.astype(jnp.int32)
    walk = _sweep_shared if shared else _sweep_per_lane
    return walk(cols, vals, lane_of, y, order, first, group_end, level_ptr,
                bound, classes=_k_classes(K), C=C)


def _sweep_per_lane(cols, vals, lane_of, y, order, first, group_end,
                    level_ptr, bound, *, classes, C):
    """:func:`trisolve_fleet`'s walk where each lane has its own plan
    row: per-lane starts, panel blocks and gathers."""
    L, n = y.shape
    stop = jnp.take_along_axis(level_ptr, bound[:, None], axis=1)[:, 0]
    lanes = jnp.arange(L)[:, None]
    cut = jnp.asarray(classes[:-1], jnp.int32)

    def branch(kc):
        # rows × slots per sweep stay at about width × the narrowest class
        rows_k = max(1, C * classes[0] // kc)

        def run(start, go, gend, y):
            end = jnp.where(go, jnp.minimum(start + rows_k, gend), start)
            # the sweep's rows are one contiguous block of the plan; near
            # the end the block starts early and its head is masked
            at = jnp.minimum(start, n - rows_k)
            pos = at[:, None] + jnp.arange(rows_k, dtype=jnp.int32)
            take = (pos >= start[:, None]) & (pos < end[:, None])
            rows = jax.vmap(lambda o, a: jax.lax.dynamic_slice(
                o, (a,), (rows_k,)))(order, at)
            rows = jnp.where(take, rows, n)

            c = jax.vmap(lambda f, a: jax.lax.dynamic_slice(
                cols, (f, a, 0), (1, rows_k, kc))[0])(lane_of, at)
            v = jax.vmap(lambda f, a: jax.lax.dynamic_slice(
                vals, (f, a, 0), (1, rows_k, kc))[0])(lane_of, at)
            upd = jnp.take_along_axis(y, jnp.minimum(rows, n - 1), axis=1) \
                - ell_spmv_fleet(c, v, y)
            return end, y.at[lanes, rows].set(upd, mode="drop")  # pads drop
        return run

    branches = [branch(kc) for kc in classes]

    def cond(carry):
        start, _ = carry
        return jnp.any(start < stop)

    def body(carry):
        start, y = carry
        live = start < stop
        at = jnp.minimum(start, n)[:, None]
        cls = jnp.sum(jnp.take_along_axis(first, at, axis=1)
                      > cut[None, :], axis=1)                     # (L,)
        run_cls = jnp.max(jnp.where(live, cls, 0))
        go = live & (cls == run_cls)
        gend = jnp.take_along_axis(group_end, at, axis=1)[:, 0]
        if len(branches) == 1:
            return branches[0](start, go, gend, y)
        return jax.lax.switch(run_cls, branches, start, go, gend, y)

    start0 = level_ptr[:, 1]
    return jax.lax.while_loop(cond, body, (start0, y))[1]


def _sweep_shared(cols, vals, lane_of, y, order, first, group_end,
                  level_ptr, bound, *, classes, C):
    """:func:`trisolve_fleet`'s walk where every lane reads stack row
    ``lane_of[0]`` and its one plan row: one start, one panel block a
    sweep, and gathers and scatters by indices shared by all lanes;
    lanes past their level bound write their old values back."""
    L, n = y.shape
    order, first, group_end, level_ptr = (
        a[0] for a in (order, first, group_end, level_ptr))
    row = lane_of[0]
    stop = level_ptr[bound]                                       # (L,)
    cut = jnp.asarray(classes[:-1], jnp.int32)
    rows_max = max(1, C)                  # the first class's row count

    def branch(kc):
        rows_k = max(1, C * classes[0] // kc)

        def run(start, live, gend, y):
            end = jnp.minimum(start + rows_k, gend)
            at = jnp.minimum(start, n - rows_k)
            pos = at + jnp.arange(rows_k, dtype=jnp.int32)
            take = (pos >= start) & (pos < end)
            rows = jnp.where(
                take, jax.lax.dynamic_slice(order, (at,), (rows_k,)), n)
            c = jax.lax.dynamic_slice(cols, (row, at, 0), (1, rows_k, kc))
            v = jax.lax.dynamic_slice(vals, (row, at, 0), (1, rows_k, kc))
            old = y[:, jnp.minimum(rows, n - 1)]
            upd = jnp.where(live[:, None],
                            old - ell_spmv_fleet(c[0], v[0], y), old)
            pad = rows_max - rows_k
            return (end, jnp.pad(rows, (0, pad), constant_values=n),
                    jnp.pad(upd, ((0, 0), (0, pad))))
        return run

    branches = [branch(kc) for kc in classes]

    def cond(carry):
        start, _ = carry
        return jnp.any(start < stop)

    def body(carry):
        start, y = carry
        at = jnp.minimum(start, n)
        cls = jnp.sum(first[at] > cut)
        args = (start, start < stop, group_end[at], y)
        if len(branches) == 1:
            end, rows, upd = branches[0](*args)
        else:
            end, rows, upd = jax.lax.switch(cls, branches, *args)
        # one scatter here, not one per branch: a branch that writes the
        # carry makes the TPU compiler copy the whole carry in it
        return end, y.at[:, rows].set(upd, mode="drop")       # pads drop

    return jax.lax.while_loop(cond, body, (level_ptr[1], y))[1]


def trisolve_panels(sched, b, flip: bool = False,
                    interpret: Optional[bool] = None):
    """Unit-triangular solve over a ``trisolve.DeviceSchedule``'s ELL
    panels, driven by the Pallas SpMV kernels — the device-built panels
    are consumed as-is (same (rows, K) tiles, no repacking).  ``b`` may
    be ``(n,)`` or ``(n, B)``; the multi-rhs kernel serves a whole block
    per level."""
    y = jnp.flip(jnp.asarray(b), axis=0) if flip else jnp.asarray(b)
    kernel = ell_spmv if y.ndim == 1 else ell_spmv_multi
    for lv in range(1, sched.n_levels):   # level-0 rows have no in-edges
        lo, hi = int(sched.row_ptr[lv]), int(sched.row_ptr[lv + 1])
        if hi == lo:
            continue
        rows = jax.lax.slice(sched.row_ids, (lo,), (hi,))
        cols = jax.lax.slice(sched.cols, (lo, 0), (hi, sched.K))
        vals = jax.lax.slice(sched.vals, (lo, 0), (hi, sched.K))
        y = y.at[rows].add(-kernel(cols, vals, y, interpret=interpret))
    return jnp.flip(y, axis=0) if flip else y

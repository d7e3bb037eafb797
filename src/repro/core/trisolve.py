"""Level-scheduled sparse triangular solves for the G D Gᵀ preconditioner.

The paper (§6.2) observes that the *critical path* of the triangular DAG —
not raw nnz — governs parallel triangular-solve performance, and that
randomized factors have dramatically shorter critical paths than classical
ones (Fig. 4).  We exploit exactly that: rows are grouped by dependency
level (level(i) = 1 + max level over in-neighbours), and each level is one
data-parallel segment-reduce.

Two schedule builders live here:

* ``build_schedules`` / ``_levels_from_edges`` — the original host
  (numpy) construction, kept as the test oracle;
* ``build_schedules_device`` — the production path: level propagation
  runs on device under ``lax.while_loop`` and the per-level panels come
  out directly in the ELL layout consumed by ``repro.kernels.spmv``, so
  the factor→preconditioner handoff never round-trips through numpy
  (the wavefront engine already leaves the factor on device).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from .ref_ac import ACFactor, DeviceFactor
# shared with the wavefront engine: one pow2 bucket-rounding policy and
# one run-rank (scatter offset) idiom across pools, schedules and fleets
from .parac import _next_pow2, _run_ranks


@dataclasses.dataclass
class LevelSchedule:
    """COO edges of a unit-triangular solve, grouped by target-row level."""

    n: int
    n_levels: int
    level_ptr: np.ndarray  # int64[n_levels+1] into the edge arrays
    e_dst: np.ndarray      # int32[nnz] — row being solved
    e_src: np.ndarray      # int32[nnz] — already-solved row it reads
    e_val: np.ndarray      # f32[nnz]
    level_of: np.ndarray   # int32[n]


def _levels_from_edges(n: int, dst: np.ndarray, src: np.ndarray,
                       val: np.ndarray) -> LevelSchedule:
    """Group solve edges by level.  Requires a topological order exists in
    which every edge goes forward; levels are computed by one sweep over
    edges sorted by dst's topological position (here: dst index order for
    the forward solve, reversed for the backward solve — callers arrange
    that dst indices are already topologically sorted)."""
    # longest-path levels via level-synchronous relaxation: converges in
    # (#levels) vectorized passes — no per-edge Python loop.
    level = np.zeros(n, np.int32)
    while True:
        cand = np.zeros(n, np.int32)
        np.maximum.at(cand, dst, level[src] + 1)
        new = np.maximum(level, cand)
        if np.array_equal(new, level):
            break
        level = new
    n_levels = int(level.max()) + 1 if n else 1
    edge_level = level[dst]
    eorder = np.argsort(edge_level, kind="stable")
    e_dst, e_src, e_val = dst[eorder], src[eorder], val[eorder]
    counts = np.bincount(edge_level[eorder], minlength=n_levels)
    level_ptr = np.zeros(n_levels + 1, np.int64)
    np.cumsum(counts, out=level_ptr[1:])
    return LevelSchedule(n=n, n_levels=n_levels, level_ptr=level_ptr,
                         e_dst=e_dst.astype(np.int32),
                         e_src=e_src.astype(np.int32),
                         e_val=e_val, level_of=level)


def build_schedules(f: ACFactor) -> Tuple[LevelSchedule, LevelSchedule]:
    """Forward (G y = r) and backward (Gᵀ x = z) level schedules.

    G is unit lower triangular in elimination positions; its CSC column k
    holds rows i > k with value G_ik.  Forward edge: (dst=i, src=k, v=G_ik)
    … wait, forward solve is  y_i = r_i − Σ_{k<i} G_ik y_k, so each CSC
    entry (i ∈ col k) is an edge dst=i, src=k.  Backward solve is
    x_k = z_k − Σ_{i>k} G_ik x_i: edge dst=k, src=i.  For the backward
    pass "topological position of dst" is n−1−k, handled by index flip.
    """
    n = f.n
    cols = np.repeat(np.arange(n, dtype=np.int32),
                     np.diff(f.col_ptr).astype(np.int64))
    fwd = _levels_from_edges(n, f.rows.astype(np.int32), cols, f.vals)
    # backward: flip indices so that ascending == reverse topological
    flip = (n - 1) - cols
    fsrc = (n - 1) - f.rows.astype(np.int32)
    bwd = _levels_from_edges(n, flip, fsrc, f.vals)
    return fwd, bwd


def solve_levels_np(sched: LevelSchedule, b: np.ndarray,
                    flip: bool = False) -> np.ndarray:
    """Host reference solve (numpy).  ``flip`` for the backward schedule
    (its indices are stored flipped)."""
    y = (b[::-1] if flip else b).astype(np.float64).copy()
    for lv in range(sched.n_levels):
        lo, hi = sched.level_ptr[lv], sched.level_ptr[lv + 1]
        if hi == lo:
            continue
        contrib = np.zeros(sched.n, np.float64)
        np.add.at(contrib, sched.e_dst[lo:hi],
                  sched.e_val[lo:hi].astype(np.float64) * y[sched.e_src[lo:hi]])
        y -= contrib
    return y[::-1] if flip else y


def make_jax_solver(sched: LevelSchedule, flip: bool = False):
    """Returns a jit-able ``b -> y`` closure; one segment-reduce per level."""
    per_level = []
    for lv in range(sched.n_levels):
        lo, hi = int(sched.level_ptr[lv]), int(sched.level_ptr[lv + 1])
        if hi == lo:
            continue
        per_level.append((jnp.asarray(sched.e_dst[lo:hi]),
                          jnp.asarray(sched.e_src[lo:hi]),
                          jnp.asarray(sched.e_val[lo:hi])))
    n = sched.n

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        y = b[::-1] if flip else b
        for dst, src, val in per_level:
            contrib = jnp.zeros(n, y.dtype).at[dst].add(val * y[src])
            y = y - contrib
        return y[::-1] if flip else y

    return solve


# ---------------------------------------------------------------------------
# Device-side schedule construction (production path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceSchedule:
    """Level schedule with rows pre-packed into ELL panels, built on
    device.  ``row_ids`` lists rows sorted by level; level ``lv`` owns
    rows ``row_ids[row_ptr[lv]:row_ptr[lv+1]]`` and the matching slabs of
    ``cols``/``vals`` — each slab is exactly the (rows, K) tile layout
    ``kernels.spmv.ell_spmv_pallas`` consumes.  Only ``row_ptr`` and
    ``n_levels`` live on host (loop bounds must be static); the data
    arrays are device-resident."""

    n: int
    n_levels: int
    K: int                  # panel width = max in-degree (≥ 1)
    row_ids: jnp.ndarray    # int32[n] — rows sorted by (level, row)
    row_ptr: np.ndarray     # int64[n_levels+1] into row_ids/cols/vals
    cols: jnp.ndarray       # int32[n, K] — in-edge sources, 0-padded
    vals: jnp.ndarray       # f32[n, K]   — in-edge values, 0-padded
    level_of: jnp.ndarray   # int32[n]


def _propagate_levels(dst, src, *, n: int):
    """Longest-path levels by iterative relaxation under ``while_loop`` —
    converges in (#levels) passes, all on device.

    Not ``@jax.jit``-wrapped: it always runs on concrete arrays under
    ``ensure_compile_time_eval`` (schedule construction is compile-time
    work, also when ``make_preconditioner`` is called inside an outer
    ``jit``), so eager dispatch costs one primitive per line, once per
    factor."""
    def cond(c):
        return c[1]

    def body(c):
        level, _ = c
        cand = jnp.zeros(n, jnp.int32).at[dst].max(level[src] + 1,
                                                   mode="drop")
        new = jnp.maximum(level, cand)
        return new, jnp.any(new != level)

    level, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros(n, jnp.int32), jnp.bool_(True)))
    return level


def _pack_ell_panels(dst, src, val, level, *, n: int, K: int):
    """Scatter solve edges into level-sorted ELL panels, one pass:
    rows sorted by level, each row's in-edges packed into its K-slot.
    Eager on purpose — see ``_propagate_levels``."""
    row_ids = jnp.argsort(level, stable=True).astype(jnp.int32)
    row_rank = jnp.zeros(n, jnp.int32).at[row_ids].set(
        jnp.arange(n, dtype=jnp.int32))
    eorder = jnp.argsort(dst, stable=True)
    sd, ss, swv = dst[eorder], src[eorder], val[eorder]
    rank = _run_ranks(sd)
    dest = row_rank[sd] * K + rank
    cols = jnp.zeros(n * K, jnp.int32).at[dest].set(ss).reshape(n, K)
    vals = jnp.zeros(n * K, val.dtype).at[dest].set(swv).reshape(n, K)
    return row_ids, cols, vals


def _schedule_from_edges_device(n: int, dst: jnp.ndarray, src: jnp.ndarray,
                                val: jnp.ndarray) -> DeviceSchedule:
    """Device schedule from COO solve edges (dst reads src).  Host work
    is limited to O(n_levels) slicing metadata — no per-edge loops.

    Schedule construction needs concrete metadata (panel width, level
    count), so it always runs at trace/compile time — callers may build
    preconditioners inside an outer ``jit`` (``ensure_compile_time_eval``
    keeps the concrete-array maths eager there).
    """
    if dst.shape[0] == 0:
        return DeviceSchedule(
            n=n, n_levels=1, K=1,
            row_ids=jnp.arange(n, dtype=jnp.int32),
            row_ptr=np.array([0, n], np.int64),
            cols=jnp.zeros((n, 1), jnp.int32),
            vals=jnp.zeros((n, 1), jnp.float32),
            level_of=jnp.zeros(n, jnp.int32))
    with jax.ensure_compile_time_eval():
        level = _propagate_levels(dst, src, n=n)
        indeg = jnp.zeros(n, jnp.int32).at[dst].add(1)
        K = max(int(indeg.max()), 1)
        row_ids, cols, vals = _pack_ell_panels(dst, src, val, level,
                                               n=n, K=K)
        level_h = np.asarray(level)        # O(n) metadata copy, no loop
    n_levels = int(level_h.max()) + 1
    row_ptr = np.searchsorted(np.sort(level_h),
                              np.arange(n_levels + 1)).astype(np.int64)
    return DeviceSchedule(n=n, n_levels=n_levels, K=K, row_ids=row_ids,
                          row_ptr=row_ptr, cols=cols, vals=vals,
                          level_of=level)


def build_schedules_device(
        f: ACFactor | DeviceFactor) -> Tuple[DeviceSchedule, DeviceSchedule]:
    """Forward/backward device schedules straight from the (device) factor.

    Edge derivation mirrors ``build_schedules``: CSC entry (i ∈ col k) is
    forward edge dst=i/src=k; the backward solve runs in flipped index
    space so ascending indices stay topological.
    """
    dev = f if isinstance(f, DeviceFactor) else f.to_device()
    n, nnz = dev.n, dev.nnz
    with jax.ensure_compile_time_eval():
        counts = jnp.diff(dev.col_ptr)
        cols_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), counts,
                             total_repeat_length=nnz)
        bsrc = (n - 1) - dev.rows
        bdst = (n - 1) - cols_of
    fwd = _schedule_from_edges_device(n, dev.rows, cols_of, dev.vals)
    bwd = _schedule_from_edges_device(n, bdst, bsrc, dev.vals)
    return fwd, bwd


# ---------------------------------------------------------------------------
# Batched (fleet) schedule construction — row-indexed panels for the
# shape-bucket mega-batching path
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PackedSchedule:
    """One triangular solve as **row-indexed** ELL panels: row ``i``'s
    in-edges occupy slot ``i`` of ``cols``/``vals`` (zero-padded to K),
    with ``level_of[i]`` its dependency level.  This is the layout the
    traced-argument solvers (``kernels.ops.trisolve_masked`` /
    ``trisolve_fleet``) consume: no level-sorted slabs, no host slicing
    metadata — the level loop masks on ``level_of`` instead, so panels
    from different factors stack into one fleet array and share one
    compiled program.  Unlike :class:`DeviceSchedule`, the backward
    schedule is kept in *original* index space (no flip): the masked
    level loop needs no topological index ordering."""

    n: int                  # true rows (rows n..n_pad are phantom)
    n_pad: int
    n_levels: int           # this factor's own level count (host int)
    K: int
    cols: jnp.ndarray       # int32[n_pad, K]
    vals: jnp.ndarray       # f32[n_pad, K]
    level_of: jnp.ndarray   # int32[n_pad] (0 for phantom rows)
    # the sweep plan (``kernels.ops.sweep_plan``): rows by level, then by
    # descending panel extent; each row's extent in that order; where
    # each run of one level and panel class ends; where each level
    # starts
    order: jnp.ndarray      # int32[n_pad]
    extent: jnp.ndarray     # int32[n_pad]
    group_end: jnp.ndarray  # int32[n_pad]
    level_ptr: jnp.ndarray  # int32[n_levels + 1]

    @property
    def device_bytes(self) -> int:
        return int(self.cols.nbytes + self.vals.nbytes
                   + self.level_of.nbytes + self.order.nbytes
                   + self.extent.nbytes + self.group_end.nbytes
                   + self.level_ptr.nbytes)

    @property
    def level_width(self) -> int:
        """Rows in the widest level ≥ 1 (level-0 rows have no in-edges
        and are never swept), the cap on :attr:`sweep_width`.  At
        least 1."""
        if self.n_levels <= 1:
            return 1
        counts = np.bincount(np.asarray(self.level_of),
                             minlength=self.n_levels)
        return max(int(counts[1:].max()), 1)

    @property
    def sweep_width(self) -> int:
        """Rows per trisolve sweep (``kernels.ops.trisolve_fleet``'s
        ``width``): the power of two at or above the mean row count of
        the levels ≥ 1, capped at the widest.  A solve then takes at
        most about twice as many sweeps as it has levels, and sweeps
        about twice as many rows as it has."""
        if self.n_levels <= 1:
            return 1
        ptr = np.asarray(self.level_ptr)
        mean = -(-int(ptr[-1] - ptr[1]) // (self.n_levels - 1))
        return max(min(_next_pow2(mean), self.level_width), 1)


def sweep_plan_np(level_of: np.ndarray, extent: np.ndarray,
                  n_levels: int):
    """Host form of ``kernels.ops.sweep_plan`` from per-row levels and
    panel extents (row-indexed): ``(order, extent in order, group_end,
    level_ptr)`` as int32 arrays."""
    n = level_of.shape[0]
    order = np.lexsort((-extent.astype(np.int64), level_of))
    ext, lv = extent[order], level_of[order]
    cls = np.maximum(np.left_shift(
        1, np.ceil(np.log2(np.maximum(ext, 1))).astype(np.int64)), 8)
    last = np.ones(n, bool)
    last[:-1] = (lv[1:] != lv[:-1]) | (cls[1:] != cls[:-1])
    ends = np.flatnonzero(last) + 1
    group_end = np.repeat(ends, np.diff(np.concatenate([[0], ends])))
    ptr = np.searchsorted(lv, np.arange(n_levels + 1), side="left")
    return (order.astype(np.int32), ext.astype(np.int32),
            group_end.astype(np.int32), ptr.astype(np.int32))


@partial(jax.jit, static_argnames=("n",))
def _propagate_levels_fleet(dst, src, *, n: int):
    """``_propagate_levels`` vmapped over a padded fleet: ``dst``/``src``
    are ``(B, E)`` with invalid (padding) edges marked ``dst == n`` so
    their relaxation drops.  One batched ``while_loop`` runs until every
    member converges — the whole fleet's level propagation is a single
    XLA program instead of B sequential ones.  Also returns each row's
    in-degree (its panel extent)."""
    levels = jax.vmap(partial(_propagate_levels, n=n))(dst, src)
    indeg = jax.vmap(lambda d: jnp.zeros(n, jnp.int32).at[d].add(
        1, mode="drop"))(dst)
    return levels, indeg


@partial(jax.jit, static_argnames=("ns", "nnzs", "n_bat", "E_bat"))
def _fleet_solve_edges(col_ptrs, rows, vals, *, ns, nnzs, n_bat: int,
                       E_bat: int):
    """The ``(2B, E_bat)`` solve-edge batch of ``build_schedules_batched``
    from B device factors' CSC arrays: forward edges (CSC entry i ∈ col
    k ⇒ dst=i, src=k) in the first B rows, backward (dst=k, src=i) in
    the last B; padding edges have ``dst == n_bat``."""
    DST, SRC, VAL = [], [], []
    for col_ptr, r, v, n, nnz in zip(col_ptrs, rows, vals, ns, nnzs):
        cols_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32),
                             jnp.diff(col_ptr), total_repeat_length=nnz)
        DST.append(_pad_dev(r.astype(jnp.int32), E_bat, n_bat))
        SRC.append(_pad_dev(cols_of, E_bat, 0))
        VAL.append(_pad_dev(v, E_bat, 0))
    for b in range(len(ns)):
        DST.append(jnp.where(DST[b] < n_bat, SRC[b], n_bat))
        SRC.append(jnp.where(DST[b] < n_bat, DST[b], 0))
    return jnp.stack(DST), jnp.stack(SRC), jnp.stack(VAL + VAL)


@partial(jax.jit, static_argnames=("n", "K"))
def _pack_row_panels_fleet(dst, src, val, *, n: int, K: int):
    """Row-indexed ELL packing, vmapped: edge ``e`` lands in slot
    ``(dst_e, rank_e)`` where rank is the edge's position within its
    dst group.  Padding edges (``dst == n``) scatter out of range and
    drop.  Mirrors ``_pack_ell_panels`` minus the level-sort indirection
    (the masked solvers index panels by row id, not level rank)."""
    def one(d, s, v):
        eorder = jnp.argsort(d, stable=True)
        sd, ss, sv = d[eorder], s[eorder], v[eorder]
        rank = _run_ranks(sd)
        dest = sd * K + rank
        cols = jnp.zeros(n * K, jnp.int32).at[dest].set(
            ss, mode="drop").reshape(n, K)
        vals = jnp.zeros(n * K, v.dtype).at[dest].set(
            sv, mode="drop").reshape(n, K)
        return cols, vals

    return jax.vmap(one)(dst, src, val)


def _pad_dev(x, size, fill):
    return jnp.concatenate(
        [x, jnp.full((size - x.shape[0],), fill, x.dtype)]) \
        if x.shape[0] != size else x


@partial(jax.jit, static_argnames=("row", "n_pad", "K"))
def _half_panels(cols, vals, levels, row: int, *, n_pad: int, K: int):
    """One solve's panels and levels, sliced out of the fleet batch to
    its own padded shape."""
    return (cols[row, :n_pad, :K], vals[row, :n_pad, :K],
            levels[row, :n_pad])


def build_schedules_batched(
        devs: "List[DeviceFactor]", *,
        device: Optional["jax.Device"] = None,
) -> List[Tuple[PackedSchedule, PackedSchedule]]:
    """Forward/backward :class:`PackedSchedule`\\ s for a whole fleet of
    device factors in one shot: the level propagation (the
    ``while_loop`` half of ``build_schedules_device``) runs **once**,
    vmapped over a ``(2B, E_pad)`` edge batch holding every factor's
    forward and backward solve edges, and the panel packing is likewise
    one vmapped scatter.  Per-factor results are sliced back to each
    factor's own power-of-two padded shape (``n_pad = pow2(n)``,
    ``K = pow2(max in-degree)``) so a factor's padded schedule is a
    function of its content alone — independent of which fleet it was
    built with.  Forward edges: CSC entry (i ∈ col k) ⇒ dst=i, src=k;
    backward: dst=k, src=i, in original index space.

    ``device`` runs the whole derivation under that accelerator's
    default placement (factor-tier replicas schedule off the serving
    devices); outputs stay uncommitted for cheap adoption elsewhere.
    """
    if device is not None:
        with jax.default_device(device):
            return build_schedules_batched(devs)
    if not devs:
        return []
    B = len(devs)
    ns = [d.n for d in devs]
    nnzs = [d.nnz for d in devs]
    n_bat = _next_pow2(max(ns))
    E_bat = max(_next_pow2(max(nnzs)), 1)
    # all inputs are concrete device buffers (DeviceFactor's contract);
    # the derivation runs as a few jitted programs (eager dispatch would
    # compile every primitive separately for each new shape)
    DSTa, SRCa, VALa = _fleet_solve_edges(
        tuple(d.col_ptr for d in devs), tuple(d.rows for d in devs),
        tuple(d.vals for d in devs), ns=tuple(ns), nnzs=tuple(nnzs),
        n_bat=n_bat, E_bat=E_bat)
    levels, indeg = _propagate_levels_fleet(DSTa, SRCa, n=n_bat)
    K_bat = max(_next_pow2(int(indeg.max())), 1)
    COLS, VALS = _pack_row_panels_fleet(DSTa, SRCa, VALa,
                                        n=n_bat, K=K_bat)
    levels_h = np.asarray(levels)
    indeg_h = np.asarray(indeg)
    kmax_h = indeg_h.max(axis=1)

    out: List[Tuple[PackedSchedule, PackedSchedule]] = []
    for b in range(B):
        halves = []
        for row in (b, B + b):               # forward, then backward
            n = ns[b]
            n_pad = _next_pow2(n)
            K = max(_next_pow2(int(kmax_h[row])), 1)
            cols, vals, lvl = _half_panels(COLS, VALS, levels, row,
                                           n_pad=n_pad, K=K)
            n_levels = int(levels_h[row, :n].max(initial=0)) + 1
            order, extent, group_end, ptr = sweep_plan_np(
                levels_h[row, :n_pad], indeg_h[row, :n_pad], n_levels)
            halves.append(PackedSchedule(
                n=n, n_pad=n_pad, n_levels=n_levels, K=K, cols=cols,
                vals=vals, level_of=lvl, order=jnp.asarray(order),
                extent=jnp.asarray(extent),
                group_end=jnp.asarray(group_end),
                level_ptr=jnp.asarray(ptr)))
        out.append((halves[0], halves[1]))
    return out


def make_ell_solver(sched: DeviceSchedule, flip: bool = False):
    """jit-able unit-triangular solve over ELL panels; accepts a single
    rhs ``(n,)`` or a multi-rhs block ``(n, nrhs)`` (one fused gather-
    multiply-reduce per level for the whole block)."""
    panels = []
    with jax.ensure_compile_time_eval():
        for lv in range(1, sched.n_levels):  # level-0 rows lack in-edges
            lo, hi = int(sched.row_ptr[lv]), int(sched.row_ptr[lv + 1])
            if hi == lo:
                continue
            panels.append(
                (jax.lax.slice(sched.row_ids, (lo,), (hi,)),
                 jax.lax.slice(sched.cols, (lo, 0), (hi, sched.K)),
                 jax.lax.slice(sched.vals, (lo, 0), (hi, sched.K))))

    def solve(b: jnp.ndarray) -> jnp.ndarray:
        y = jnp.flip(b, axis=0) if flip else b
        for rows, cols, vals in panels:
            gathered = y[cols]                       # (R, K[, nrhs])
            v = vals if y.ndim == 1 else vals[:, :, None]
            contrib = jnp.sum(v * gathered, axis=1)
            y = y.at[rows].add(-contrib)             # rows touched once
        return jnp.flip(y, axis=0) if flip else y

    return solve


def make_preconditioner_from_schedules(fwd: DeviceSchedule,
                                       bwd: DeviceSchedule, D: jnp.ndarray):
    """``r -> (G D Gᵀ)⁺ r`` from pre-built device schedules (the Solver
    path: schedules are built once per factor and shared)."""
    fsolve = make_ell_solver(fwd)
    bsolve = make_ell_solver(bwd, flip=True)
    with jax.ensure_compile_time_eval():
        dinv = jnp.where(D > 0, 1.0 / jnp.where(D > 0, D, 1.0), 0.0)

    def apply(r: jnp.ndarray) -> jnp.ndarray:
        y = fsolve(r)
        z = y * (dinv if y.ndim == 1 else dinv[:, None])
        return bsolve(z)

    return apply


def make_preconditioner(f: ACFactor | DeviceFactor):
    """jit-able ``r -> (G D Gᵀ)⁺ r`` via two level-scheduled solves.

    Built from the device schedules (no numpy round-trip); supports a
    single rhs ``(n,)`` or a multi-rhs block ``(n, nrhs)``.
    """
    fwd, bwd = build_schedules_device(f)
    dev = f if isinstance(f, DeviceFactor) else f.to_device()
    return make_preconditioner_from_schedules(fwd, bwd, dev.D)


def precond_apply_np(f: ACFactor, r: np.ndarray) -> np.ndarray:
    fwd, bwd = build_schedules(f)
    y = solve_levels_np(fwd, r)
    dinv = np.where(f.D > 0, 1.0 / np.where(f.D > 0, f.D, 1.0), 0.0)
    return solve_levels_np(bwd, y * dinv, flip=True)

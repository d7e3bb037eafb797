"""``FactorCache`` / ``Solver`` — the device-resident factor→solve
pipeline as a multi-tenant API.

The paper's production shape is *factor once, serve many solves*: the
randomized construction is cheap (little pre-processing, §4) and the
short-critical-path factor (§6.2) then amortizes over every rhs that
arrives.  A service amortizes further by keeping **many** live factors:

    cache = FactorCache(memory_budget_bytes=1 << 28)
    gid = cache.factor(graph, jax.random.key(0)).graph_id
    res = cache.solve(gid, b)        # route by graph id
    res = cache.solve(gid, B)        # (nrhs, n) block → batched PCG

``factor`` runs the wavefront engine, compacts the factor on device,
derives both triangular level schedules on device, and **admits the
factor to its shape-bucket fleet**: a :class:`FactorFleet` keyed by
``n_pad = pow2(n)`` that stacks every member's padded Laplacian edges,
row-indexed trisolve panels and D⁻¹ into one ``pcg.FleetArrays`` block.
Solves — direct ``FactorHandle.solve`` and the continuous-batching
``serve.SolveEngine`` alike — pass those arrays as **traced arguments**
to shared fleet PCG programs, so every factor in a bucket shares one
compiled step program and the two paths take bit-identical per-lane
iterates.  ``factor_batched`` admits a whole fleet in two batched XLA
programs (vmapped wavefront + vmapped schedule construction).

The cache itself is an LRU keyed by a content fingerprint of
``(graph, key)``; it evicts whole handles when the device-memory budget
is exceeded and supports per-handle staleness (``ttl_s`` wall-clock /
``max_age_ticks`` service ticks, clock injectable for tests) so a
resubmitted *modified* graph ages its ancestor fingerprint out instead
of accumulating near-duplicates under the budget.

``Solver`` keeps the original single-tenant surface (``factor`` then
``solve(B)`` against the most recent handle) as a thin subclass.
"""
from __future__ import annotations

import dataclasses
from functools import partial
import hashlib
import heapq
import time
import weakref
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.runtime import pad_k
from repro.obs.flight import NULL_FLIGHT
from repro.obs.tracing import note_program, span
from .laplacian import Graph
from .ref_ac import ACFactor, DeviceFactor
from .parac import factorize_wavefront, factorize_batched, _next_pow2
from .trisolve import PackedSchedule, build_schedules_batched, _pad_dev
from .ichol import ichol_device_factor
from .amg import amg_ell_precond
from .spai import EllPrecond, spai_ell_precond
from .pcg import (PCGResult, FleetArrays, fleet_matvec,
                  fleet_precondition, pcg_fleet_solve, pcg_fleet_result)


_UNSET = object()


def graph_fingerprint(g: Graph, key: Optional[jax.Array] = None, *,
                      family: str = "ac",
                      params: Optional[Dict] = None) -> str:
    """Content hash of a graph (and optionally the factorization key,
    preconditioner family and construction params) — the cache identity
    of a preconditioner.  Two structurally identical systems built the
    same way share a fingerprint, so resubmitting a known graph is a
    cache hit; the same graph under two families (or two droptols) gets
    two distinct fingerprints and two cache rows.

    Args:
        g: the graph.
        key: factorization PRNG key (randomized families only).
        family: preconditioner family name (``"ac"`` leaves the hash
            identical to the historical graph-only fingerprint).
        params: family construction parameters (hashed by sorted repr).

    Returns:
        Hex digest string.
    """
    h = hashlib.blake2b(digest_size=12)
    h.update(np.int64(g.n).tobytes())
    h.update(np.ascontiguousarray(g.src).tobytes())
    h.update(np.ascontiguousarray(g.dst).tobytes())
    h.update(np.ascontiguousarray(g.w).tobytes())
    if key is not None:
        h.update(np.ascontiguousarray(jax.random.key_data(key)).tobytes())
    if family != "ac" or params:
        h.update(family.encode())
        h.update(repr(sorted((params or {}).items())).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Preconditioner family registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PrecondFamily:
    """One registered preconditioner family.

    ``kind`` selects the fleet's **static** apply program (see
    ``pcg.fleet_precondition``): ``"factor"`` families ship a
    ``(G, D)`` triangular factor and apply via two masked fleet
    trisolves; ``"spmv"`` families ship a materialized approximate
    inverse in ELL rows and apply via one lane-batched SpMV.  ``build``
    constructs the host/device payload: ``build(g, key, dtype=...,
    **params)`` returning either an ``ACFactor``/``DeviceFactor``
    (factor kind) or an :class:`~repro.core.spai.EllPrecond` (spmv
    kind)."""

    name: str
    kind: str
    build: Callable


PRECOND_FAMILIES: Dict[str, PrecondFamily] = {}


def register_family(name: str, kind: str, build: Callable) -> PrecondFamily:
    """Register (or replace) a preconditioner family.

    Args:
        name: family name (``FactorCache.factor(..., family=name)``).
        kind: ``"factor"`` or ``"spmv"``.
        build: constructor ``(g, key, *, dtype, **params) -> payload``.

    Returns:
        The registered :class:`PrecondFamily`.

    Raises:
        ValueError: unknown ``kind``.
    """
    if kind not in ("factor", "spmv"):
        raise ValueError(f"unknown apply kind {kind!r}")
    fam = PrecondFamily(name=name, kind=kind, build=build)
    PRECOND_FAMILIES[name] = fam
    return fam


def get_family(name: str) -> PrecondFamily:
    """Look up a registered family.

    Raises:
        KeyError: no family registered under ``name``.
    """
    fam = PRECOND_FAMILIES.get(name)
    if fam is None:
        raise KeyError(f"unknown preconditioner family {name!r} "
                       f"(registered: {sorted(PRECOND_FAMILIES)})")
    return fam


register_family(
    "ac", "factor",
    # the randomized AC construction is special-cased in
    # ``FactorCache.factor`` (it alone batches through
    # ``factorize_batched``); this builder is the single-graph path
    lambda g, key, *, dtype=np.float32, chunk=64, fill_slack=32,
    strict=True: factorize_wavefront(
        g, key, chunk=chunk, fill_slack=fill_slack, strict=strict,
        dtype=dtype))
register_family(
    "ichol", "factor",
    lambda g, key, *, dtype=np.float32, droptol=0.0, max_shift_tries=8:
    ichol_device_factor(g, droptol=droptol,
                        max_shift_tries=max_shift_tries, dtype=dtype))
register_family(
    "amg", "spmv",
    lambda g, key, *, dtype=np.float32, droptol=1e-3:
    amg_ell_precond(g, droptol=droptol, dtype=dtype))
register_family(
    "spai", "spmv",
    lambda g, key, *, dtype=np.float32, droptol=0.0:
    spai_ell_precond(g, droptol=droptol, dtype=dtype))


def _pad1(x: jnp.ndarray, size: int) -> jnp.ndarray:
    """Zero-pad a 1-D device array to ``size`` (shared fill-pad helper
    lives in ``trisolve._pad_dev``)."""
    return _pad_dev(x, size, 0)


def _grow(x: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Zero-pad ``x`` up to ``shape`` (every axis grows or stays)."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, shape)])


@partial(jax.jit, static_argnames=("m_pad", "n_pad"))
def _padded_edges(src, dst, w, D, *, m_pad: int, n_pad: int):
    """A factor's Laplacian edge lists zero-padded to ``m_pad`` and its
    inverse diagonal (0 where ``D <= 0``) to ``n_pad``."""
    dinv = jnp.where(D > 0, 1.0 / jnp.where(D > 0, D, 1.0), 0.0)
    return (_pad1(src, m_pad), _pad1(dst, m_pad), _pad1(w, m_pad),
            _pad1(dinv, n_pad))


class _PaddedFactor:
    """One preconditioner's bucket-padded device arrays, ready for fleet
    admission: padded Laplacian edge lists, forward/backward
    :class:`PackedSchedule` panels and the padded inverse diagonal.

    ``"spmv"``-kind members reuse the same container: the approximate
    inverse's ELL rows ride in the *forward* panel slots (level 0
    everywhere — the SpMV apply never runs the level loop), the
    backward panels are inert 1-wide zeros and ``dinv`` is zero."""

    __slots__ = ("n", "n_pad", "src", "dst", "w", "fwd", "bwd", "dinv")

    def __init__(self, g: Graph, dev: DeviceFactor, fwd: PackedSchedule,
                 bwd: PackedSchedule):
        self.n = g.n
        self.n_pad = fwd.n_pad
        m_pad = max(_next_pow2(g.m), 1)
        w = np.asarray(g.w).astype(dev.vals.dtype)
        self.src, self.dst, self.w, self.dinv = _padded_edges(
            np.asarray(g.src, np.int32), np.asarray(g.dst, np.int32), w,
            dev.D, m_pad=m_pad, n_pad=self.n_pad)
        self.fwd = fwd
        self.bwd = bwd

    @classmethod
    def from_ell(cls, g: Graph, op: EllPrecond) -> "_PaddedFactor":
        """Build the fleet-admissible view of a materialized approximate
        inverse: the ELL rows become a 1-level forward panel (padding
        rows/slots carry zero values, so they contribute exactly zero to
        the lane-batched SpMV)."""
        n_pad = max(_next_pow2(g.n), 1)
        with jax.ensure_compile_time_eval():
            cols = _grow(jnp.asarray(op.cols, jnp.int32), (n_pad, op.K))
            vals = _grow(jnp.asarray(op.vals), (n_pad, op.K))
            zeros_n = jnp.zeros((n_pad,), jnp.int32)
            # one level: the plan is the identity order (never swept)
            plan = dict(order=jnp.arange(n_pad, dtype=jnp.int32),
                        extent=zeros_n,
                        group_end=jnp.full((n_pad,), n_pad, jnp.int32),
                        level_ptr=jnp.asarray([0, n_pad], jnp.int32))
            fwd = PackedSchedule(n=g.n, n_pad=n_pad, n_levels=1, K=op.K,
                                 cols=cols, vals=vals, level_of=zeros_n,
                                 **plan)
            bwd = PackedSchedule(
                n=g.n, n_pad=n_pad, n_levels=1, K=1,
                cols=jnp.zeros((n_pad, 1), jnp.int32),
                vals=jnp.zeros((n_pad, 1), vals.dtype),
                level_of=zeros_n, **plan)
            dev = DeviceFactor(col_ptr=jnp.zeros((g.n + 1,), jnp.int32),
                               rows=jnp.zeros((0,), jnp.int32),
                               vals=jnp.zeros((0,), vals.dtype),
                               D=jnp.zeros((g.n,), vals.dtype))
        return cls(g, dev, fwd, bwd)


def _row_payload(pf: _PaddedFactor) -> FleetArrays:
    """One factor's fleet row, field by field, before padding: panels
    row-indexed (``_fleet_admit`` puts them in sweep-plan order)."""
    return FleetArrays(
        src=pf.src, dst=pf.dst, w=pf.w,
        fcols=pf.fwd.cols, fvals=pf.fwd.vals, flevel=pf.fwd.level_of,
        bcols=pf.bwd.cols, bvals=pf.bwd.vals, blevel=pf.bwd.level_of,
        dinv=pf.dinv, nvalid=np.int32(pf.n),
        fnlv=np.int32(pf.fwd.n_levels), bnlv=np.int32(pf.bwd.n_levels),
        forder=pf.fwd.order, fext=pf.fwd.extent, fgend=pf.fwd.group_end,
        fptr=pf.fwd.level_ptr, border=pf.bwd.order, bext=pf.bwd.extent,
        bgend=pf.bwd.group_end, bptr=pf.bwd.level_ptr)


@partial(jax.jit, static_argnames=("shapes",))
def _fleet_admit(a: Optional[FleetArrays], ix, rows, *, shapes):
    """Grow a fleet stack to ``shapes`` (zero padding; level counts pad
    with 1) and write ``rows`` (from ``_row_payload``) at stack rows
    ``ix``, panels permuted into their sweep plans' order.  One program
    per shape set: dispatched op by op, an admission compiled some
    sixty primitives for every new bucket shape."""
    ones = ("fnlv", "bnlv")
    plan_of = {"fcols": "forder", "fvals": "forder",
               "bcols": "border", "bvals": "border"}
    out = {}
    for name, shape in zip(FleetArrays._fields, shapes):
        new = []
        for r in rows:
            x = jnp.asarray(getattr(r, name))
            if name in plan_of:
                x = jnp.take(x, getattr(r, plan_of[name]), axis=0)
            new.append(_grow(x, shape[1:]))
        new = jnp.stack(new)
        if a is None:
            base = jnp.full(shape, 1 if name in ones else 0, new.dtype)
        else:
            base = _grow(getattr(a, name), shape)
            if name in ones:
                base = jnp.maximum(base, 1)
        out[name] = base.at[ix].set(new)
    return FleetArrays(**out)


class FactorFleet:
    """Stacked, bucket-padded device preconditioners for one
    ``(family, shape-bucket, K-tier)`` (``n_pad = pow2(n)``; ``k_tier``
    the padded panel-width tier — see :meth:`FactorCache` K-tiering),
    plus the row bookkeeping that lets handles come and go.  ``kind`` is
    the fleet's static apply program (``"factor"`` trisolves /
    ``"spmv"``); a fleet never mixes kinds, so every member shares one
    compiled step program.  Sub-bucketing by K-tier keeps one hub-heavy
    factor (huge in-degree ⇒ wide trisolve panels) from inflating every
    bucket-mate's ``(n_pad, K)`` sweep to its width.

    ``arrays`` is the live :class:`pcg.FleetArrays` stack — the traced
    factor argument of every fleet PCG program.  Rows are claimed by
    weak reference: a row frees itself when its owning handle dies (an
    engine pinning an evicted handle keeps the row alive through the
    same reference) — the weakref callback pushes the row onto an O(1)
    free-heap — and admission reuses dead rows before growing the
    stack, so fleet memory is bounded by the peak number of *live*
    handles in the bucket, not by churn.  Growth along any axis
    (capacity, ``m_pad``, panel width ``K``) zero-pads — padding edges
    carry zero weight and padded panel slots zero values, so existing
    members' solves are unchanged.  :meth:`compact` is the inverse:
    it rebuilds the stack to the live rows so long-lived caches'
    ``fleet_device_bytes`` tracks live factors, not the high-water
    mark; every compaction bumps ``generation`` so engines holding
    device-resident lane state can re-sync their row indices.
    """

    def __init__(self, n_pad: int, family: str = "ac",
                 kind: str = "factor", k_tier: int = 0,
                 device: Optional[jax.Device] = None):
        self.n_pad = n_pad
        self.family = family
        self.kind = kind
        self.k_tier = k_tier       # padded panel-width tier (0 = untiered)
        # pinned accelerator for the stack (None = default device): every
        # stack rebuild commits `arrays` here, so the jitted fleet
        # programs that take them as traced args run on this device —
        # a cluster pins each replica's fleets to its own device
        self.device = device
        self.m_pad = 1
        self.Kf = 1
        self.Kb = 1
        self.f_levels = 1          # bucket-wide static level bounds
        self.b_levels = 1
        self.f_width = 1           # bucket-wide rows per trisolve sweep
        self.b_width = 1
        self.generation = 0        # bumped by compact(): row indices moved
        self.compactions = 0
        self.arrays: Optional[FleetArrays] = None
        self._rows: List[Optional[weakref.ref]] = []
        self._free: List[int] = []              # min-heap of dead rows
        self._ref2row: Dict[weakref.ref, int] = {}

    @property
    def capacity(self) -> int:
        return 0 if self.arrays is None else int(self.arrays.nvalid.shape[0])

    @property
    def apply_statics(self) -> Dict:
        """The static arguments of this fleet's PCG programs
        (``pcg.fleet_precondition``): apply kind, bucket-wide level
        bounds and rows per trisolve sweep.  Each distinct value compiles
        once."""
        return dict(kind=self.kind, f_levels=self.f_levels,
                    b_levels=self.b_levels, f_width=self.f_width,
                    b_width=self.b_width)

    def index_path(self, lanes: int) -> str:
        """The index path a fleet program over ``lanes`` lanes takes on
        this stack (``kernels.ops.shares_index``): ``"shared"`` where
        every lane reads one row, else ``"per_lane"``."""
        from repro.kernels.ops import shares_index
        return "shared" if shares_index(self.capacity, lanes) \
            else "per_lane"

    @property
    def live_rows(self) -> int:
        return sum(r is not None and r() is not None for r in self._rows)

    @property
    def free_rows(self) -> int:
        """Rows admittable without growing the stack: dead rows awaiting
        reuse (the free-heap) plus pow2 capacity slack past the current
        end."""
        return len(self._free) + max(self.capacity - len(self._rows), 0)

    @property
    def bytes_per_row(self) -> int:
        if self.arrays is None:
            return 0
        return sum(int(x.nbytes) // x.shape[0] for x in self.arrays)

    @property
    def device_bytes(self) -> int:
        """Total resident footprint of the stack — including dead rows
        awaiting reuse and pow2 capacity slack.  The stack is grow-only
        (rows recycle, axes never shrink: in-flight lanes hold row
        indices into it), so this can exceed the sum of live handles'
        per-row accounting; ``FactorCache.stats()`` surfaces it as
        ``fleet_device_bytes`` so budget users see the true number."""
        return 0 if self.arrays is None else \
            sum(int(x.nbytes) for x in self.arrays)

    @property
    def resident_device(self) -> Optional[str]:
        """Where the stack actually lives (read from the arrays, not the
        pin request) — ``None`` before the first admission.  The
        multi-device placement test asserts this matches the replica's
        assigned device."""
        if self.arrays is None:
            return None if self.device is None else str(self.device)
        return str(next(iter(self.arrays.src.devices())))

    def _row_died(self, ref: weakref.ref,
                  _push=heapq.heappush) -> None:
        """Weakref callback: the handle owning ``ref``'s row was
        collected — recycle the row onto the free-heap.  Refs retired by
        a :meth:`compact` are no longer in ``_ref2row`` and fall
        through harmlessly.  ``heappush`` is bound at definition: the
        callback also fires while the interpreter tears modules down."""
        row = self._ref2row.pop(ref, None)
        if row is not None and row < len(self._rows) \
                and self._rows[row] is ref:
            self._rows[row] = None
            _push(self._free, row)

    def _free_rows(self, k: int) -> List[int]:
        """Claim ``k`` distinct rows: recycled dead rows (ascending —
        heap pops) first, then fresh rows past the current end.  Every
        heap row precedes every fresh row, so the result is ascending by
        construction.  O(k log F) amortized — the old linear scan over
        the whole row list paid O(F) per admission once churn left dead
        rows scattered through a large stack."""
        rows: List[int] = []
        while len(rows) < k and self._free:
            rows.append(heapq.heappop(self._free))
        nxt = len(self._rows)
        while len(rows) < k:
            rows.append(nxt)
            nxt += 1
        return rows

    def admit(self, handle: "FactorHandle", pf: _PaddedFactor) -> int:
        """Claim a row for ``pf`` (reusing a dead row when possible) and
        scatter its arrays into the stack.  Returns the row index."""
        return self.admit_many([(handle, pf)])[0]

    def admit_many(self, pairs: Sequence[Tuple["FactorHandle",
                                               _PaddedFactor]]
                   ) -> List[int]:
        """Admit ``B`` factors in one stack update: the bucket grows
        **once** to the batch-wide ``(capacity, m_pad, K)`` envelope and
        every new row lands in a single scatter per field — O(B) device
        copies where per-factor ``admit`` paid O(B²) (each ``.at[].set``
        copies the whole stack).  Row claiming, growth envelopes and
        padded row contents are identical to ``B`` sequential admits
        (growth only ever zero-pads), so the resulting stack is
        bit-identical either way.  Returns the claimed row indices, in
        ``pairs`` order."""
        if not pairs:
            return []
        assert all(pf.n_pad == self.n_pad for _, pf in pairs)
        m_pad = max(self.m_pad, *(pf.src.shape[0] for _, pf in pairs))
        Kf = max(self.Kf, *(pf.fwd.K for _, pf in pairs))
        Kb = max(self.Kb, *(pf.bwd.K for _, pf in pairs))
        f_levels = max(self.f_levels, *(pf.fwd.n_levels for _, pf in pairs))
        b_levels = max(self.b_levels, *(pf.bwd.n_levels for _, pf in pairs))
        rows = self._free_rows(len(pairs))
        F = max(_next_pow2(max(rows) + 1), self.capacity)
        np_ = self.n_pad
        pf0 = pairs[0][1]
        shapes = FleetArrays(
            src=(F, m_pad), dst=(F, m_pad), w=(F, m_pad),
            fcols=(F, np_, Kf), fvals=(F, np_, Kf), flevel=(F, np_),
            bcols=(F, np_, Kb), bvals=(F, np_, Kb), blevel=(F, np_),
            dinv=(F, np_), nvalid=(F,), fnlv=(F,), bnlv=(F,),
            forder=(F, np_), fext=(F, np_), fgend=(F, np_),
            fptr=(F, f_levels + 1), border=(F, np_), bext=(F, np_),
            bgend=(F, np_), bptr=(F, b_levels + 1))
        self.arrays = _fleet_admit(
            self.arrays, jnp.asarray(np.asarray(rows, np.int32)),
            tuple(_row_payload(pf) for _, pf in pairs),
            shapes=tuple(shapes))
        if self.device is not None:
            # commit the rebuilt stack to the pinned device (no-op copy
            # once resident: growth/scatter of committed arrays already
            # ran there; only brand-new capacity pays a real transfer).
            # Committed arrays also pin every downstream jitted solve —
            # an adopted factor built on another device lands here.
            self.arrays = jax.device_put(self.arrays, self.device)
        self.m_pad, self.Kf, self.Kb = m_pad, Kf, Kb
        self.f_levels, self.b_levels = f_levels, b_levels
        if self.kind == "factor":
            self.f_width = max(self.f_width,
                               *(pf.fwd.sweep_width for _, pf in pairs))
            self.b_width = max(self.b_width,
                               *(pf.bwd.sweep_width for _, pf in pairs))
        for (handle, _), row in zip(pairs, rows):
            ref = weakref.ref(handle, self._row_died)
            self._ref2row[ref] = row
            if row == len(self._rows):     # rows ascending: appends in order
                self._rows.append(ref)
            else:
                self._rows[row] = ref
        return rows

    def compact(self) -> int:
        """Rebuild the stack to its live rows: one gather per fleet
        array down to the live set, capacity re-padded to
        ``pow2(live)``.  Live handles' ``fleet_row`` indices are
        rewritten in place (their strong refs are held for the duration,
        so no row dies mid-rebuild) and ``generation`` is bumped so an
        engine holding device-resident lane state keyed by old row
        indices re-scatters its ``fidx`` before the next step.  Row
        *contents* are copied verbatim, so every live handle's solve is
        bit-identical before and after.  Returns the number of freed
        stack rows (0 when the stack is already at its pow2 floor)."""
        if self.arrays is None:
            return 0
        live: List[Tuple[int, "PreconditionerHandle"]] = []
        for i, r in enumerate(self._rows):
            h = r() if r is not None else None
            if h is not None:
                live.append((i, h))
        old_cap = self.capacity
        new_cap = max(_next_pow2(len(live)), 1)
        if new_cap >= old_cap:
            return 0
        old_idx = np.fromiter((i for i, _ in live), np.int32,
                              count=len(live))
        with jax.ensure_compile_time_eval():
            ix = jnp.asarray(old_idx)
            self.arrays = FleetArrays(*(
                _grow(x[ix], (new_cap,) + tuple(x.shape[1:]))
                for x in self.arrays))
        if self.device is not None:
            self.arrays = jax.device_put(self.arrays, self.device)
        freed = old_cap - new_cap
        self._ref2row.clear()               # retire old refs (callbacks
        self._free = []                     # on them become no-ops)
        self._rows = []
        for new_row, (_, h) in enumerate(live):
            h.fleet_row = new_row
            ref = weakref.ref(h, self._row_died)
            self._ref2row[ref] = new_row
            self._rows.append(ref)
        self.generation += 1
        self.compactions += 1
        return freed


@dataclasses.dataclass(eq=False)
class PreconditionerHandle:
    """A constructed preconditioner ready to serve solves — the one
    interface every family (randomized AC, ichol, AMG, SPAI) presents
    to the cache, the engine and direct callers: construct (via
    ``FactorCache.factor``) → apply (``precondition``/``solve``) →
    ``device_bytes`` → staleness (``ttl_s``/``max_age_ticks``).

    The hot-path data lives in the handle's ``(family, shape-bucket)``
    :class:`FactorFleet` (``fleet`` + ``fleet_row``) as stacked,
    bucket-padded device arrays; solves pass them as traced arguments to
    the shared fleet PCG programs (with the fleet's static apply
    ``kind``), so two handles in one fleet share compiled code.  Jitted
    solve closures are cached per rhs-batch shape in a bounded LRU."""

    graph: Graph
    factor: object          # family payload: ACFactor | DeviceFactor
    fleet: FactorFleet      # | EllPrecond
    fleet_row: int
    n_levels_fwd: int
    n_levels_bwd: int
    graph_id: str = ""
    family: str = "ac"
    construct_s: float = 0.0   # wall-clock construction cost (seconds)
    max_cached_solves: int = 16
    born_s: float = 0.0
    born_tick: int = 0
    ttl_s: Optional[float] = None
    max_age_ticks: Optional[int] = None
    _cache: "OrderedDict[Tuple, Callable]" = dataclasses.field(
        default_factory=OrderedDict)
    _sweeps: Optional[Tuple[Tuple, int]] = None
    # solves dispatched, by index path (FactorFleet.index_path)
    dispatches: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"shared": 0, "per_lane": 0})

    @property
    def n(self) -> int:
        return self.graph.n

    @property
    def n_pad(self) -> int:
        return self.fleet.n_pad

    @property
    def kind(self) -> str:
        """The fleet's static apply kind (``"factor"`` | ``"spmv"``)."""
        return self.fleet.kind

    @property
    def n_levels(self) -> int:
        """Forward critical-path length (levels) — the §6.2 figure of
        merit surfaced by benchmarks (1 for ``"spmv"`` families: their
        apply is level-free)."""
        return self.n_levels_fwd

    @property
    def device_bytes(self) -> int:
        """Device-memory footprint the :class:`FactorCache` budget
        accounts: the handle's row of the fleet stack (padded edges,
        both panel sets, D⁻¹) plus the family payload's own device
        residency (the compact device factor for factor kinds; spmv
        payloads are host-side, their device copy *is* the fleet
        row)."""
        f = self.factor
        if isinstance(f, (ACFactor, DeviceFactor)):
            dev = f.to_device()
            own = sum(int(a.nbytes)
                      for a in (dev.col_ptr, dev.rows, dev.vals, dev.D))
        else:
            own = 0
        return own + self.fleet.bytes_per_row

    @property
    def sweeps_per_apply(self) -> int:
        """Sweeps of one forward plus one backward triangular solve of
        this factor for one lane, as ``trisolve_fleet`` runs them: the
        sweep plan the fleet stores, walked on first read (and again
        once the fleet's panel or sweep widths change).  0 for
        ``"spmv"`` kinds, whose apply runs no sweep."""
        fl = self.fleet
        if fl.kind != "factor":
            return 0
        key = (fl.Kf, fl.Kb, fl.f_width, fl.b_width, fl.f_levels,
               fl.b_levels)
        if self._sweeps is None or self._sweeps[0] != key:
            from repro.kernels.ops import trisolve_sweeps
            a, row = fl.arrays, self.fleet_row
            # whole host copies: an eager slice compiles a program
            row_of = lambda x: np.asarray(x)[row]  # noqa: E731
            count = sum(
                trisolve_sweeps(row_of(ext), row_of(gend), row_of(ptr),
                                int(row_of(nlv)), K, width)
                for ext, gend, ptr, nlv, K, width in (
                    (a.fext, a.fgend, a.fptr, a.fnlv, fl.Kf, fl.f_width),
                    (a.bext, a.bgend, a.bptr, a.bnlv, fl.Kb, fl.b_width)))
            self._sweeps = (key, count)
        return self._sweeps[1]

    def matvec(self, x: jnp.ndarray) -> jnp.ndarray:
        """``L x`` through the handle's fleet row (the padded edge lists
        already resident in the bucket stack — no per-handle copies)."""
        fa = self.fleet.arrays
        Y = jnp.zeros((1, self.n_pad), x.dtype).at[0, :self.n].set(x)
        return fleet_matvec(fa, self._fidx(1), Y)[0, :self.n]

    def _fidx(self, L: int) -> jnp.ndarray:
        return jnp.full((L,), self.fleet_row, jnp.int32)

    def precondition(self, r: jnp.ndarray) -> jnp.ndarray:
        """Apply this preconditioner: ``r -> (G D Gᵀ)⁺ r`` for factor
        kinds, ``r -> M r`` for spmv kinds, for ``r`` of shape ``(n,)``
        or ``(n, nrhs)`` — the fleet apply routed through this handle's
        fleet row (columns become lanes)."""
        fa = self.fleet.arrays
        statics = self.fleet.apply_statics
        n, n_pad = self.n, self.n_pad
        if r.ndim == 1:
            R = jnp.zeros((1, n_pad), r.dtype).at[0, :n].set(r)
            out = fleet_precondition(fa, self._fidx(1), R, **statics)
            return out[0, :n]
        R = jnp.zeros((r.shape[1], n_pad), r.dtype).at[:, :n].set(r.T)
        out = fleet_precondition(fa, self._fidx(r.shape[1]), R, **statics)
        return out[:, :n].T

    def solve(self, B, *, tol: float = 1e-6, maxiter: int = 1000,
              project: bool = True) -> PCGResult:
        """PCG-solve ``L x = b``.  ``B``: ``(n,)`` for one rhs or
        ``(nrhs, n)`` for a batch (all columns share this factor).
        Runs the fleet PCG one-shot loop over the handle's bucket
        arrays — the same body a :class:`serve.SolveEngine` ticks, so a
        served request reproduces these iterates bit-exactly."""
        with span("solver/solve"):
            B = jnp.asarray(B)
            if B.ndim not in (1, 2) or B.shape[-1] != self.n:
                raise ValueError(
                    f"rhs must be (n,) or (nrhs, n) with n={self.n}, "
                    f"got {B.shape}")
            statics = self.fleet.apply_statics
            key = (B.shape, str(B.dtype), float(tol), int(maxiter), project,
                   *sorted(statics.items()))
            fn = self._cache.get(key)
            if fn is None:
                fn = jax.jit(self._build_solve(B.ndim, tol, maxiter, project,
                                               statics))
                self._cache[key] = fn
                while len(self._cache) > self.max_cached_solves:
                    self._cache.popitem(last=False)
            else:
                self._cache.move_to_end(key)
            args = (B, self.fleet.arrays, jnp.int32(self.fleet_row))
            path = self.fleet.index_path(1 if B.ndim == 1 else B.shape[0])
            self.dispatches[path] += 1
            with span(f"fleet/{path}"):
                out = fn(*args)
            note_program(fn, *args)
            return out

    def _build_solve(self, ndim: int, tol: float, maxiter: int,
                     project: bool, statics: Dict):
        # the fleet row rides in as a traced argument, not a closure
        # constant: a fleet compaction may move this handle to a new row
        # at any time, and the cached compiled solve must follow it
        n, n_pad = self.n, self.n_pad

        def run(B, fa, row):
            B2 = B if ndim == 2 else B[None]
            L = B2.shape[0]
            Bp = jnp.zeros((L, n_pad), B2.dtype).at[:, :n].set(B2)
            state = pcg_fleet_solve(
                fa, jnp.full((L,), row, jnp.int32), Bp,
                jnp.full((L,), tol, jnp.float32),
                jnp.full((L,), maxiter, jnp.int32),
                project=project, **statics)
            res = pcg_fleet_result(state, n)
            if ndim == 1:
                return PCGResult(x=res.x[0], iters=res.iters[0],
                                 relres=res.relres[0],
                                 converged=res.converged[0])
            return res

        return run


# Historical name: every pre-zoo call site (and the serving engine's
# type hints) used ``FactorHandle``; the interface is unchanged for the
# AC family, so the alias is permanent API.
FactorHandle = PreconditionerHandle


class FactorCache:
    """Multi-tenant factor-once / solve-many frontend.

    Construction options are fixed per cache.  ``factor`` (or
    ``factor_batched`` / ``attach``) admits handles keyed by graph
    fingerprint; ``solve(graph_id, B)`` routes a rhs to its factor.
    Admission evicts least-recently-used handles while the summed
    ``device_bytes`` exceeds ``memory_budget_bytes`` (or the handle
    count exceeds ``max_handles``) — the newest handle is never evicted.

    Staleness: handles admitted with ``ttl_s`` (seconds, against the
    injected ``clock``) or ``max_age_ticks`` (service ticks, advanced by
    ``advance_ticks`` — a serving engine calls it once per tick) expire
    on the next lookup/admission sweep, so resubmitting a modified graph
    ages its ancestor fingerprint out of the budget.  Defaults (``None``)
    never expire.
    """

    def __init__(self, *, chunk: int = 64, fill_slack: int = 32,
                 strict: bool = True,
                 dtype=np.float32,
                 memory_budget_bytes: Optional[int] = None,
                 max_handles: Optional[int] = None,
                 max_cached_solves: int = 16,
                 ttl_s: Optional[float] = None,
                 max_age_ticks: Optional[int] = None,
                 k_tiering: bool = True,
                 compact_threshold: Optional[float] = 0.5,
                 device: Optional[jax.Device] = None,
                 clock: Optional[Callable[[], float]] = None,
                 flight=None):
        self.chunk = chunk
        self.fill_slack = fill_slack
        self.strict = strict
        self.dtype = dtype
        self.memory_budget_bytes = memory_budget_bytes
        self.max_handles = max_handles
        self.max_cached_solves = max_cached_solves
        self.ttl_s = ttl_s
        self.max_age_ticks = max_age_ticks
        # K-tiering sub-buckets fleets by padded panel width so a
        # hub-heavy member can't inflate narrow bucket-mates' panels;
        # False collapses every width into tier 0 (the pre-tiering
        # layout — kept for A/B benchmarking of the padding tax)
        self.k_tiering = k_tiering
        # compact a fleet when free_rows/capacity reaches this after an
        # eviction/expiry sweep (None = never compact)
        self.compact_threshold = compact_threshold
        # accelerator this cache's fleet stacks are pinned to (None =
        # default device).  Committing the stacks commits every jitted
        # fleet program that traces them, so one process can run N
        # caches on N devices with the router as the only cross-device
        # hop (see docs/architecture.md, disaggregation)
        self.device = device
        self._clock = clock if clock is not None else time.monotonic
        self.now_ticks = 0
        # one-way latch: True once any handle was admitted/refreshed
        # with a staleness policy — lets sweep_stale() stay O(1) on the
        # per-submit hot path of services that never use TTLs
        self._has_mortal = False
        self._handles: "OrderedDict[str, PreconditionerHandle]" = \
            OrderedDict()
        # family-heterogeneous: one fleet per (family, shape bucket,
        # K-tier) — families never share a stack, so each keeps its own
        # compiled step program and its own per-row memory accounting
        self._fleets: Dict[Tuple[str, int, int], FactorFleet] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.expirations = 0
        self.compactions = 0
        self.adoptions = 0         # factors constructed elsewhere, adopted
        # flight-recorder events for cache lifecycle transitions — a
        # post-mortem needs the eviction/expiry/compaction sequence that
        # preceded an incident, not just the end-state counters
        fl = flight if flight is not None else NULL_FLIGHT
        self._ev_cache_evict = fl.bind("cache_evict")
        self._ev_cache_expire = fl.bind("cache_expire")
        self._ev_compaction = fl.bind("compaction")
        self._ev_adopt = fl.bind("adopt")

    # -- staleness ----------------------------------------------------------
    def advance_ticks(self, k: int = 1) -> None:
        """Advance the service tick clock (engines call this per tick)."""
        self.now_ticks += k

    def _stale(self, h: FactorHandle, now_s: float) -> bool:
        if h.ttl_s is not None and now_s - h.born_s > h.ttl_s:
            return True
        if h.max_age_ticks is not None and \
                self.now_ticks - h.born_tick > h.max_age_ticks:
            return True
        return False

    def _refresh_policy(self, h: FactorHandle, ttl_s, max_age_ticks) -> None:
        """Explicit staleness arguments on a cache *hit* re-admit the
        handle: its policy is replaced and its birth stamps reset, so
        ``factor(..., ttl_s=...)`` means the same thing whether it
        factors or hits."""
        if ttl_s is _UNSET and max_age_ticks is _UNSET:
            return
        if ttl_s is not _UNSET:
            h.ttl_s = ttl_s
        if max_age_ticks is not _UNSET:
            h.max_age_ticks = max_age_ticks
        h.born_s = self._clock()
        h.born_tick = self.now_ticks
        if h.ttl_s is not None or h.max_age_ticks is not None:
            self._has_mortal = True

    def sweep_stale(self) -> int:
        """Evict every expired handle; returns how many were evicted.
        Runs automatically on admission and ``get`` lookups (O(1) until
        a staleness policy is first used)."""
        if not self._has_mortal:
            return 0
        now_s = self._clock()
        stale = [gid for gid, h in self._handles.items()
                 if self._stale(h, now_s)]
        for gid in stale:
            del self._handles[gid]
            self.expirations += 1
            self._ev_cache_expire(gid=gid)
        if stale:
            self._maybe_compact()
        return len(stale)

    def _maybe_compact(self) -> int:
        """Compact every fleet whose dead-row fraction crossed
        ``compact_threshold`` (called after evictions/expiries).
        Returns how many fleets were compacted."""
        if self.compact_threshold is None:
            return 0
        done = 0
        for fleet in self._fleets.values():
            cap = fleet.capacity
            if cap and fleet.free_rows / cap >= self.compact_threshold:
                if fleet.compact():
                    self.compactions += 1
                    self._ev_compaction(family=fleet.family,
                                        n_pad=fleet.n_pad,
                                        k_tier=fleet.k_tier)
                    done += 1
        return done

    def compact(self) -> int:
        """Unconditionally compact every fleet to its live rows
        (threshold ignored — the automatic trigger only fires on
        eviction/expiry sweeps, which can miss rows whose last external
        reference died later).  Returns how many fleets shrank."""
        done = 0
        for fleet in self._fleets.values():
            if fleet.compact():
                self.compactions += 1
                self._ev_compaction(family=fleet.family,
                                    n_pad=fleet.n_pad,
                                    k_tier=fleet.k_tier)
                done += 1
        return done

    # -- admission ----------------------------------------------------------
    def factor(self, g: Graph, key: jax.Array, *,
               graph_id: Optional[str] = None, family: str = "ac",
               precond_params: Optional[Dict] = None, ttl_s=_UNSET,
               max_age_ticks=_UNSET) -> PreconditionerHandle:
        """Construct a preconditioner for ``g`` and admit the handle
        (cache hit if an identical ``(graph, key, family, params)`` is
        already live and fresh).

        Args:
            g: graph to precondition.
            key: factorization PRNG key (ignored by deterministic
                families — ichol/amg/spai — but still part of the
                default fingerprint only for ``family="ac"``).
            graph_id: explicit cache key (defaults to the content
                fingerprint including family and params).
            family: registered preconditioner family
                (``"ac"``/``"ichol"``/``"amg"``/``"spai"``).
            precond_params: family construction parameters (e.g.
                ``{"droptol": 0.02}`` for icholt).
            ttl_s / max_age_ticks: staleness policy overrides.

        Returns:
            The admitted (or refreshed) :class:`PreconditionerHandle`.

        Raises:
            KeyError: ``family`` is not registered.
        """
        self.sweep_stale()
        fam = get_family(family)
        params = dict(precond_params or {})
        gid = graph_id if graph_id is not None else graph_fingerprint(
            g, key if family == "ac" else None, family=family,
            params=params)
        got = self._handles.get(gid)
        if got is not None:
            self.hits += 1
            self._handles.move_to_end(gid)
            self._refresh_policy(got, ttl_s, max_age_ticks)
            return got
        self.misses += 1
        t0 = time.perf_counter()
        if family == "ac":
            f = factorize_wavefront(
                g, key, chunk=self.chunk, fill_slack=self.fill_slack,
                strict=self.strict,
                dtype=self.dtype, **params)
        else:
            f = fam.build(g, key, dtype=self.dtype, **params)
        handle = self.attach(g, f, graph_id=gid, family=family,
                             ttl_s=ttl_s, max_age_ticks=max_age_ticks)
        handle.construct_s = time.perf_counter() - t0
        return handle

    def factor_batched(self, gs: Sequence[Graph], keys, *,
                       graph_ids: Optional[Sequence[str]] = None,
                       ttl_s=_UNSET, max_age_ticks=_UNSET
                       ) -> List[FactorHandle]:
        """Admit a fleet: graphs not already cached factor together in
        one vmapped XLA program (``parac.factorize_batched``) and their
        trisolve schedules derive in one vmapped pass alongside."""
        self.sweep_stale()
        gs = list(gs)
        if not isinstance(keys, jax.Array):
            keys = jnp.stack(list(keys))
        gids = list(graph_ids) if graph_ids is not None else [
            graph_fingerprint(g, keys[i]) for i, g in enumerate(gs)]
        todo = [i for i, gid in enumerate(gids) if gid not in self._handles]
        self.hits += len(gs) - len(todo)
        self.misses += len(todo)
        for gid in set(gids) - {gids[i] for i in todo}:
            self._refresh_policy(self._handles[gid], ttl_s, max_age_ticks)
        # strong refs for the whole call: a tight budget may LRU-evict a
        # sibling of this very fleet mid-admission — the caller still gets
        # every handle back (evicted ones simply aren't cached any more).
        fleet = {gid: self._handles[gid] for gid in gids
                 if gid in self._handles}
        if todo:
            fs, scheds = factorize_batched(
                [gs[i] for i in todo], jnp.stack([keys[i] for i in todo]),
                chunk=self.chunk, fill_slack=self.fill_slack,
                strict=self.strict,
                dtype=self.dtype, with_schedules=True)
            admitted = self._attach_many(
                [(gs[i], f, sch, gids[i], "ac")
                 for i, f, sch in zip(todo, fs, scheds)],
                ttl_s=ttl_s, max_age_ticks=max_age_ticks)
            fleet.update(admitted)
        for gid in gids:
            if gid in self._handles:
                self._handles.move_to_end(gid)
        return [fleet[gid] for gid in gids]

    def attach(self, g: Graph, f, *,
               graph_id: Optional[str] = None, family: str = "ac",
               schedules: Optional[Tuple[PackedSchedule,
                                         PackedSchedule]] = None,
               ttl_s=_UNSET, max_age_ticks=_UNSET) -> PreconditionerHandle:
        """Wrap an existing family payload (e.g. a factor from the
        sequential oracle, or a pre-built ``EllPrecond``) in a solve
        handle and admit it to its ``(family, shape-bucket)`` fleet —
        same lifecycle, no re-construction.

        Args:
            g: the payload's graph.
            f: family payload (``ACFactor``/``DeviceFactor`` for factor
                kinds, ``EllPrecond`` for spmv kinds).
            graph_id: explicit cache key (defaults to the graph+family
                fingerprint).
            family: registered family name (selects the fleet kind).
            schedules: short-circuits the per-factor schedule build
                when a batched one already ran (factor kinds only).
            ttl_s / max_age_ticks: staleness policy overrides.

        Returns:
            The admitted :class:`PreconditionerHandle`.
        """
        gid = graph_id if graph_id is not None else graph_fingerprint(
            g, family=family)
        (_, handle), = self._attach_many([(g, f, schedules, gid, family)],
                                         ttl_s=ttl_s,
                                         max_age_ticks=max_age_ticks)
        return handle

    def adopt(self, g: Graph, f, *, graph_id: str, family: str = "ac",
              schedules: Optional[Tuple[PackedSchedule,
                                        PackedSchedule]] = None,
              construct_s: float = 0.0, ttl_s=_UNSET,
              max_age_ticks=_UNSET) -> PreconditionerHandle:
        """Admit a preconditioner **constructed elsewhere** (a factor-tier
        replica, another process): the adopt path is device transfer +
        fleet-row scatter only — it never factors.  A live fresh handle
        for ``graph_id`` short-circuits as a hit (adopt is idempotent, so
        a tier shipping a factor that raced a colocated construction
        cannot double-claim fleet rows); otherwise the payload rides the
        normal ``attach`` lifecycle — ``admit_many`` commits its arrays
        to this cache's pinned device, which is where the cross-device
        hop happens.

        Args:
            g: the payload's graph.
            f: family payload (see :meth:`attach`).
            graph_id: cache key the factor was constructed under.
            family: registered family name.
            schedules: packed trisolve schedules built alongside the
                factor (skips the per-factor schedule build entirely).
            construct_s: construction wall-clock on the factor tier,
                recorded on the handle so telemetry attributes it there.
            ttl_s / max_age_ticks: staleness policy overrides.

        Returns:
            The adopted (or already-resident) handle.
        """
        self.sweep_stale()
        got = self._handles.get(graph_id)
        if got is not None:
            self.hits += 1
            self._handles.move_to_end(graph_id)
            self._refresh_policy(got, ttl_s, max_age_ticks)
            return got
        handle = self.attach(g, f, graph_id=graph_id, family=family,
                             schedules=schedules, ttl_s=ttl_s,
                             max_age_ticks=max_age_ticks)
        handle.construct_s = construct_s
        self.adoptions += 1
        self._ev_adopt(gid=graph_id, family=family,
                       construct_s=construct_s)
        return handle

    def _attach_many(self, items: Sequence[Tuple[Graph, object,
                                                 Optional[Tuple],
                                                 str, str]],
                     *, ttl_s=_UNSET, max_age_ticks=_UNSET
                     ) -> List[Tuple[str, PreconditionerHandle]]:
        """Admit a batch of ``(graph, payload, schedules|None, gid,
        family)``: members are grouped by ``(family, shape bucket)`` and
        each fleet's stack grows **once**, scattering all its new rows
        in one update (:meth:`FactorFleet.admit_many`) — per-factor
        ``attach`` in a loop pays O(B²) device copies for B same-bucket
        admissions.  Handles register in ``items`` order (LRU order
        preserved); the budget sweep runs once at the end."""
        built: List[Tuple[FactorFleet, PreconditionerHandle,
                          _PaddedFactor, str]] = []
        for g, f, schedules, gid, family in items:
            fam = get_family(family)
            if fam.kind == "spmv":
                with span("construct/pack"):
                    pf = _PaddedFactor.from_ell(g, f)
                fwd, bwd = pf.fwd, pf.bwd
            else:
                dev = f.to_device()
                if schedules is None:
                    with span("construct/schedules"):
                        schedules = build_schedules_batched([dev])[0]
                fwd, bwd = schedules
                with span("construct/pack"):
                    pf = _PaddedFactor(g, dev, fwd, bwd)
            # pow2 K-tier on the padded panel width (max of both panel
            # sets — the tier must cover whichever trisolve is wider);
            # tier 0 = tiering disabled, one fleet per (family, n_pad)
            k_tier = pad_k(max(fwd.K, bwd.K)) if self.k_tiering else 0
            fkey = (family, pf.n_pad, k_tier)
            fleet = self._fleets.get(fkey)
            if fleet is None:
                fleet = self._fleets[fkey] = FactorFleet(
                    pf.n_pad, family=family, kind=fam.kind, k_tier=k_tier,
                    device=self.device)
            handle = PreconditionerHandle(
                graph=g, factor=f, fleet=fleet, fleet_row=-1,
                n_levels_fwd=fwd.n_levels, n_levels_bwd=bwd.n_levels,
                graph_id=gid, family=family,
                max_cached_solves=self.max_cached_solves,
                born_s=self._clock(), born_tick=self.now_ticks,
                ttl_s=self.ttl_s if ttl_s is _UNSET else ttl_s,
                max_age_ticks=(self.max_age_ticks
                               if max_age_ticks is _UNSET
                               else max_age_ticks))
            built.append((fleet, handle, pf, gid))
        by_fleet: Dict[Tuple[str, int, int],
                       List[Tuple[PreconditionerHandle,
                                  _PaddedFactor]]] = {}
        for fleet, handle, pf, _ in built:
            by_fleet.setdefault((fleet.family, fleet.n_pad, fleet.k_tier),
                                []).append((handle, pf))
        for fkey, pairs in by_fleet.items():
            with span("construct/admit"):
                rows = self._fleets[fkey].admit_many(pairs)
            for (handle, _), row in zip(pairs, rows):
                handle.fleet_row = row
        out: List[Tuple[str, FactorHandle]] = []
        for _, handle, _, gid in built:
            if handle.ttl_s is not None or handle.max_age_ticks is not None:
                self._has_mortal = True
            self._handles[gid] = handle
            self._handles.move_to_end(gid)
            out.append((gid, handle))
        self._shrink()
        return out

    def _shrink(self):
        """Evict LRU handles until budget/count bounds hold (the newest
        handle always survives)."""
        evicted = False
        while len(self._handles) > 1 and (
                (self.max_handles is not None
                 and len(self._handles) > self.max_handles)
                or (self.memory_budget_bytes is not None
                    and self.device_bytes > self.memory_budget_bytes)):
            gid, _ = self._handles.popitem(last=False)
            self.evictions += 1
            self._ev_cache_evict(gid=gid, reason="budget")
            evicted = True
        if evicted:
            self._maybe_compact()

    # -- lookup / routing ---------------------------------------------------
    def peek(self, graph_id: str) -> Optional[FactorHandle]:
        """Non-faulting lookup that does not touch LRU order or sweep
        staleness (lets a serving engine check whether its pinned handle
        is still the cached one)."""
        return self._handles.get(graph_id)

    def fresh(self, graph_id: str) -> bool:
        """Non-mutating freshness probe: True iff ``graph_id`` has a live
        handle that would *not* be swept as stale on the next lookup.
        Unlike ``get`` it never sweeps, never touches LRU order and only
        reads — safe for a cluster router to call from outside the
        engine's driver thread."""
        h = self._handles.get(graph_id)
        return h is not None and not self._stale(h, self._clock())

    def capacity_probe(self) -> Dict[str, Optional[int]]:
        """Read-only headroom snapshot for cluster placement decisions:
        how much more factor state this cache can admit before evicting.
        ``free_bytes``/``free_handles`` are ``None`` when the matching
        bound is unset (unbounded); ``fleet_free_rows`` counts bucket
        rows reusable without growing any stack.

        Called from router threads while the serving driver thread may
        be admitting — the handle/fleet dicts are snapshotted with
        ``list()`` (one GIL-atomic copy) before iteration, so a
        concurrent insert can never raise mid-iteration; the numbers
        are advisory and may be one admission stale."""
        handles = list(self._handles.values())
        fleets = list(self._fleets.values())
        used = sum(h.device_bytes for h in handles)
        free_bytes = None if self.memory_budget_bytes is None else \
            max(self.memory_budget_bytes - used, 0)
        free_handles = None if self.max_handles is None else \
            max(self.max_handles - len(handles), 0)
        return dict(handles=len(handles),
                    free_handles=free_handles,
                    device_bytes=used,
                    free_bytes=free_bytes,
                    fleet_free_rows=sum(f.free_rows for f in fleets))

    def get(self, graph_id: str) -> FactorHandle:
        self.sweep_stale()
        handle = self._handles.get(graph_id)
        if handle is None:
            raise KeyError(f"no live factor for graph_id={graph_id!r} "
                           f"({len(self._handles)} cached)")
        self._handles.move_to_end(graph_id)
        return handle

    def __contains__(self, graph_id: str) -> bool:
        return graph_id in self._handles

    def __len__(self) -> int:
        return len(self._handles)

    @property
    def graph_ids(self) -> List[str]:
        return list(self._handles)

    @property
    def device_bytes(self) -> int:
        return sum(h.device_bytes for h in self._handles.values())

    @property
    def fleets(self) -> Dict[Tuple[str, int, int], FactorFleet]:
        """Live fleets keyed by ``(family, n_pad, k_tier)`` (read-only
        view)."""
        return dict(self._fleets)

    def evict(self, graph_id: str) -> None:
        if self._handles.pop(graph_id, None) is not None:
            self.evictions += 1
            self._ev_cache_evict(gid=graph_id, reason="explicit")
            self._maybe_compact()

    def clear(self) -> None:
        self._handles.clear()

    def stats(self) -> Dict:
        """Cache counters plus per-family memory accounting.

        Returns:
            Dict with hit/miss/eviction/``compactions`` counters, total
            and per-family ``device_bytes`` (``device_bytes_by_family``
            / ``handles_by_family``), the fleet-stack footprint
            (``fleet_device_bytes``, also split by family) and the live
            floor it compacts toward (``fleet_live_bytes`` = live rows
            × per-row bytes — the CI memory invariant compares the
            two).
        """
        # snapshot with list() (GIL-atomic copies): cluster telemetry
        # reads these from router threads while the driver may admit
        handles = list(self._handles.values())
        fleet_items = list(self._fleets.items())
        by_family_bytes: Dict[str, int] = {}
        by_family_handles: Dict[str, int] = {}
        for h in handles:
            by_family_bytes[h.family] = \
                by_family_bytes.get(h.family, 0) + h.device_bytes
            by_family_handles[h.family] = \
                by_family_handles.get(h.family, 0) + 1
        fleet_by_family: Dict[str, int] = {}
        for (family, _, _), f in fleet_items:
            fleet_by_family[family] = \
                fleet_by_family.get(family, 0) + f.device_bytes
        # actual placement attribution (read from the arrays, not the
        # pin request): the multi-device gate sums bytes per device
        fleet_by_device: Dict[str, int] = {}
        for _, f in fleet_items:
            dev = f.resident_device
            if dev is not None and f.device_bytes:
                fleet_by_device[dev] = \
                    fleet_by_device.get(dev, 0) + f.device_bytes
        return dict(handles=len(handles), hits=self.hits,
                    misses=self.misses, evictions=self.evictions,
                    expirations=self.expirations,
                    compactions=self.compactions,
                    adoptions=self.adoptions,
                    device=(str(self.device)
                            if self.device is not None else None),
                    fleet_device_bytes_by_device=fleet_by_device,
                    fleets=len(fleet_items),
                    device_bytes=sum(h.device_bytes for h in handles),
                    fleet_device_bytes=sum(f.device_bytes
                                           for _, f in fleet_items),
                    fleet_live_bytes=sum(f.live_rows * f.bytes_per_row
                                         for _, f in fleet_items),
                    handles_by_family=by_family_handles,
                    device_bytes_by_family=by_family_bytes,
                    fleet_device_bytes_by_family=fleet_by_family)

    def solve(self, graph_id: str, B, **kw) -> PCGResult:
        return self.get(graph_id).solve(B, **kw)


class Solver(FactorCache):
    """Single-tenant compatibility surface over :class:`FactorCache`:
    ``factor``/``attach`` remember the most recent handle and ``solve``
    takes just the rhs.  Defaults to ``max_handles=1`` so factoring a
    sweep of graphs through one ``Solver`` keeps O(1) device memory,
    exactly like the pre-cache ``Solver`` did."""

    def __init__(self, **kw):
        kw.setdefault("max_handles", 1)
        super().__init__(**kw)
        self.handle: Optional[FactorHandle] = None

    def factor(self, g: Graph, key: jax.Array, **kw) -> FactorHandle:
        self.handle = super().factor(g, key, **kw)
        return self.handle

    def attach(self, g: Graph, f: ACFactor, **kw) -> FactorHandle:
        self.handle = super().attach(g, f, **kw)
        return self.handle

    def solve(self, B, **kw) -> PCGResult:
        if self.handle is None:
            raise RuntimeError("Solver.solve before Solver.factor")
        return self.handle.solve(B, **kw)

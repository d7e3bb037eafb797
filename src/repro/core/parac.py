"""ParAC — bulk-synchronous wavefront randomized Cholesky (JAX).

TPU-native adaptation of the paper's GPU persistent-kernel algorithm
(Algorithm 4).  Each round:

  1. the *ready set* (dep == 0, not eliminated) is an independent set of
     the current multi-graph — take the ``chunk`` smallest labels;
  2. gather their column slabs from the static edge pool, eliminate them
     all at once (``vmap`` of the shared per-column math; the Pallas
     ``sample_clique`` kernel is the tiled version of the same math);
  3. write the normalized column back in place (the pool doubles as the
     output factor, like the paper's array O);
  4. bulk-scatter sampled spanning-tree edges to their owner column's
     slab at sort-derived offsets (the barrier-free analogue of the
     paper's ``hash(a) + fill_in_count(a)`` insertion);
  5. update dependency counters with segment adds (the atomic-free
     analogue of Algorithm 4 lines 21/24).

Rounds iterate under ``lax.while_loop`` until every vertex is eliminated.
The factor is bit-identical to the sequential oracle because per-vertex
randomness is schedule independent (``column_math.column_uniforms``).

The round is decomposed into pure stage functions (`_round_ready`,
`_round_eliminate`, `_round_commit`, `_round_scatter`) composed by
``_engine_round``; ``_run_engine`` drives one graph and
``_run_engine_batched`` ``vmap``s the same round over a padded fleet —
``factorize_batched`` factors B Laplacians in one XLA program and is
bit-identical to per-graph ``factorize_wavefront`` because the factor is
schedule- and padding-width-independent (phantom vertices start
eliminated; phantom pool slots belong to zero-capacity columns).

Memory model (paper §5.1): one static pool sized ``m + n·fill_slack``;
column k owns slab ``[col_base[k], col_base[k] + cap[k])``.  Overflowing
sampled edges are dropped *and counted* — `strict=True` retries with a
doubled slack instead (dynamic malloc is as ill-advised in XLA as in
device code).  In the batched path only the overflowing graphs re-run
(masked re-runs at doubled slack); converged graphs keep their result.

The doubling is bounded: eliminating a vertex with ``d`` merged
neighbours removes its ``d`` edges and samples ``d − 1``, so the graph
never holds more than ``m`` live edges, and a slab can never need more
than ``m`` slots.  At ``fill_slack ≥ m`` nothing overflows
(:func:`_next_strict_slack`); a strict run that still drops an edge
there raises instead of returning a factor with dropped fill.
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.tracing import span
from .laplacian import Graph
from .column_math import eliminate_column, column_uniforms, INVALID_ID
from .ref_ac import ACFactor, DeviceFactor


class EngineState(NamedTuple):
    pool_row: jnp.ndarray   # int32[P] — max-label endpoint / factor row id
    pool_val: jnp.ndarray   # f32[P]   — alive: edge weight (>0); done: G value
    col_fill: jnp.ndarray   # int32[n] — #entries in each column slab
    dep: jnp.ndarray        # int32[n] — #alive multi-edges with max endpoint v
    elim: jnp.ndarray       # bool[n]
    D: jnp.ndarray          # f32[n]
    n_elim: jnp.ndarray     # int32
    n_rounds: jnp.ndarray   # int32
    overflow: jnp.ndarray   # int32 — dropped sampled edges (0 in strict runs)


# ---------------------------------------------------------------------------
# Pure per-round stages (shared verbatim by the single-graph and batched
# engines — the batched path must not fork the math)
# ---------------------------------------------------------------------------

def _init_state(pool_row, pool_val, col_fill, dep,
                elim0: Optional[jnp.ndarray] = None) -> EngineState:
    """Fresh engine state.  ``elim0`` pre-eliminates vertices (the padded
    batched path marks phantom vertices eliminated so they never enter a
    ready set)."""
    n = col_fill.shape[0]
    elim = jnp.zeros(n, bool) if elim0 is None else elim0
    return EngineState(
        pool_row=pool_row, pool_val=pool_val, col_fill=col_fill, dep=dep,
        elim=elim, D=jnp.zeros(n, pool_val.dtype),
        n_elim=jnp.sum(elim).astype(jnp.int32), n_rounds=jnp.int32(0),
        overflow=jnp.int32(0))


def _round_ready(elim: jnp.ndarray, dep: jnp.ndarray, *, chunk: int):
    """Stage 1 — the ready set: ``chunk`` smallest ready labels.  Returns
    candidate labels and their validity mask (short rounds pad with
    invalid candidates)."""
    n = elim.shape[0]
    labels = jnp.arange(n, dtype=jnp.int32)
    prio = jnp.where((~elim) & (dep == 0), labels, n)
    _, cand = jax.lax.top_k(-prio, chunk)
    cand = cand.astype(jnp.int32)
    return cand, prio[cand] < n


def _round_eliminate(s: EngineState, cand, cand_ok, col_base, key, *,
                     dmax: int):
    """Stage 2 — gather candidate column slabs and eliminate them all at
    once.  Returns the per-column elimination results plus the gathered
    slab geometry the commit stage writes back through."""
    P = s.pool_row.shape[0]
    offs = jnp.arange(dmax, dtype=jnp.int32)
    base = col_base[cand]
    fill = s.col_fill[cand]
    slots = base[:, None] + offs[None, :]
    sv = (offs[None, :] < fill[:, None]) & cand_ok[:, None]
    slots_c = jnp.where(sv, slots, P)
    ids = jnp.take(s.pool_row, slots_c, mode="fill", fill_value=INVALID_ID)
    ws = jnp.take(s.pool_val, slots_c, mode="fill", fill_value=0.0)
    u = jax.vmap(lambda v: column_uniforms(key, v, dmax))(cand)
    res = jax.vmap(eliminate_column)(ids, ws, sv, u)
    return res, slots, sv, ids


def _round_commit(s: EngineState, cand, cand_ok, res, slots, sv, ids, *,
                  dmax: int):
    """Stages 3+4 — write normalized factor columns in place and decrement
    dependency counters for the consumed multi-edges."""
    n = s.col_fill.shape[0]
    P = s.pool_row.shape[0]
    offs = jnp.arange(dmax, dtype=jnp.int32)
    wmask = (offs[None, :] < res.m[:, None]) & cand_ok[:, None]
    tgt = jnp.where(wmask, slots, P).ravel()
    pool_row = s.pool_row.at[tgt].set(res.g_rows.ravel(), mode="drop")
    pool_val = s.pool_val.at[tgt].set(res.g_vals.ravel(), mode="drop")
    col_fill = s.col_fill.at[cand].set(
        jnp.where(cand_ok, res.m, s.col_fill[cand]))
    D = s.D.at[cand].set(jnp.where(cand_ok, res.ell_kk, s.D[cand]))
    elim = s.elim.at[cand].set(cand_ok | s.elim[cand])
    dep = s.dep.at[jnp.where(sv, ids, n).ravel()].add(-1, mode="drop")
    return pool_row, pool_val, col_fill, dep, elim, D


def _run_ranks(sorted_keys: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its run of equal consecutive keys
    (keys must already be sorted/grouped; device-side analogue of
    ``_cumcount``).  The shared scatter-offset idiom of the engine's
    sampled-edge scatter and the trisolve schedule builders' ELL
    packers — one implementation so the run-boundary handling cannot
    drift between them."""
    E = sorted_keys.shape[0]
    eidx = jnp.arange(E, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_keys[1:] != sorted_keys[:-1]])
    run_start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, eidx, 0))
    return eidx - run_start


def _round_scatter(pool_row, pool_val, col_fill, dep, res, cand_ok,
                   col_base, cap, overflow):
    """Stage 5 — scatter sampled spanning-tree edges to their owner
    column's slab at sort-derived offsets; edges past a slab's capacity
    are dropped and counted in ``overflow``."""
    n = col_fill.shape[0]
    P = pool_row.shape[0]
    e_valid = (res.e_valid & cand_ok[:, None]).ravel()
    e_lo = jnp.where(e_valid, res.e_lo.ravel(), n)
    e_hi = res.e_hi.ravel()
    e_w = res.e_w.ravel()
    order = jnp.argsort(e_lo, stable=True)
    so, sh, sw2 = e_lo[order], e_hi[order], e_w[order]
    rank = _run_ranks(so)
    valid_e = so < n
    dst_fill = jnp.take(col_fill, jnp.minimum(so, n - 1))
    slot = jnp.take(col_base, jnp.minimum(so, n - 1)) + dst_fill + rank
    fits = valid_e & (dst_fill + rank < jnp.take(cap, jnp.minimum(so, n - 1)))
    overflow = overflow + jnp.sum(valid_e & ~fits)
    tgt_e = jnp.where(fits, slot, P)
    pool_row = pool_row.at[tgt_e].set(sh, mode="drop")
    pool_val = pool_val.at[tgt_e].set(sw2, mode="drop")
    col_fill = col_fill.at[jnp.where(fits, so, n)].add(1, mode="drop")
    dep = dep.at[jnp.where(fits, sh, n)].add(1, mode="drop")
    return pool_row, pool_val, col_fill, dep, overflow


def _engine_round(s: EngineState, col_base, cap, key, *, dmax: int,
                  chunk: int) -> EngineState:
    """One bulk-synchronous round — the composition of the pure stages."""
    cand, cand_ok = _round_ready(s.elim, s.dep, chunk=chunk)
    res, slots, sv, ids = _round_eliminate(s, cand, cand_ok, col_base, key,
                                           dmax=dmax)
    pool_row, pool_val, col_fill, dep, elim, D = _round_commit(
        s, cand, cand_ok, res, slots, sv, ids, dmax=dmax)
    pool_row, pool_val, col_fill, dep, overflow = _round_scatter(
        pool_row, pool_val, col_fill, dep, res, cand_ok, col_base, cap,
        s.overflow)
    return EngineState(
        pool_row=pool_row, pool_val=pool_val, col_fill=col_fill,
        dep=dep, elim=elim, D=D,
        n_elim=s.n_elim + jnp.sum(cand_ok).astype(jnp.int32),
        n_rounds=s.n_rounds + 1, overflow=overflow)


def _engine_cond(s: EngineState):
    n = s.elim.shape[0]
    return (s.n_elim < n) & (s.n_rounds <= n)


@partial(jax.jit, static_argnames=("dmax", "chunk"))
def _run_engine(pool_row, pool_val, col_fill, dep, col_base, cap, key,
                *, dmax: int, chunk: int) -> EngineState:
    state = _init_state(pool_row, pool_val, col_fill, dep)
    return jax.lax.while_loop(
        _engine_cond,
        lambda s: _engine_round(s, col_base, cap, key, dmax=dmax,
                                chunk=chunk),
        state)


@partial(jax.jit, static_argnames=("dmax", "chunk"))
def _run_engine_batched(pool_row, pool_val, col_fill, dep, col_base, cap,
                        elim0, keys, *, dmax: int, chunk: int) -> EngineState:
    """The wavefront ``while_loop`` under ``vmap``: one XLA program
    factors the whole padded fleet.  Graphs whose predicate goes false
    freeze (vmap-of-while masks their updates) while the rest keep
    iterating, so each graph takes exactly its own round sequence."""
    def one(pr, pv, cf, dp, cb, cp, e0, key):
        state = _init_state(pr, pv, cf, dp, e0)
        return jax.lax.while_loop(
            _engine_cond,
            lambda s: _engine_round(s, cb, cp, key, dmax=dmax, chunk=chunk),
            state)

    return jax.vmap(one)(pool_row, pool_val, col_fill, dep, col_base, cap,
                         elim0, keys)


@jax.jit
def _compact_pool(pool_row, pool_val, col_fill, col_base):
    """Device-side CSC compaction: squeeze each column's live slab prefix
    into contiguous CSC order.  One vectorized pass (ownership lookup via
    searchsorted over slab bases + masked scatter) — the jit replacement
    for the old ``for k in range(n)`` host loop.

    Returns pool-sized ``rows_c``/``vals_c`` whose first ``col_ptr[-1]``
    entries are the compact factor, plus ``col_ptr`` (int32[n+1]).
    """
    P = pool_row.shape[0]
    slot = jnp.arange(P, dtype=jnp.int32)
    # owner column of each pool slot (zero-cap slabs are skipped because
    # consecutive equal bases collapse under side="right")
    owner = (jnp.searchsorted(col_base, slot, side="right") - 1).astype(
        jnp.int32)
    off = slot - col_base[owner]
    keep = off < col_fill[owner]
    col_ptr = jnp.concatenate([
        jnp.zeros(1, jnp.int32), jnp.cumsum(col_fill, dtype=jnp.int32)])
    dest = jnp.where(keep, col_ptr[owner] + off, P)
    rows_c = jnp.zeros(P, pool_row.dtype).at[dest].set(pool_row, mode="drop")
    vals_c = jnp.zeros(P, pool_val.dtype).at[dest].set(pool_val, mode="drop")
    return rows_c, vals_c, col_ptr


def _build_pool(g: Graph, fill_slack: int, dtype):
    """Static slab layout: cap_k = owned-initial-degree + fill_slack."""
    n = g.n
    owned = np.zeros(n, np.int64)
    np.add.at(owned, g.src, 1)
    cap = owned + fill_slack
    col_base = np.zeros(n + 1, np.int64)
    np.cumsum(cap, out=col_base[1:])
    P = int(col_base[-1])
    pool_row = np.full(P, INVALID_ID, np.int32)
    pool_val = np.zeros(P, dtype)
    fill = np.zeros(n, np.int64)
    # place initial edges at the head of their owner slab
    idx = col_base[g.src] + _cumcount(g.src, n)
    pool_row[idx] = g.dst
    pool_val[idx] = g.w.astype(dtype)
    fill[: n] = owned
    dep = np.zeros(n, np.int64)
    np.add.at(dep, g.dst, 1)
    dmax = int(cap.max()) if n else 1
    return (pool_row, pool_val, fill.astype(np.int32), dep.astype(np.int32),
            col_base.astype(np.int32), cap.astype(np.int32), P, dmax)


def _cumcount(keys: np.ndarray, n: int) -> np.ndarray:
    """Occurrence rank of each element within its key group (keys arbitrary order)."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    start = np.concatenate([[True], sk[1:] != sk[:-1]])
    run_start = np.maximum.accumulate(np.where(start, np.arange(sk.size), 0))
    rank_sorted = np.arange(sk.size) - run_start
    rank = np.empty_like(rank_sorted)
    rank[order] = rank_sorted
    return rank


@partial(jax.jit, static_argnames=("n", "nnz"))
def _factor_slices(col_ptr, rows, vals, D, *, n: int, nnz: int):
    """The compacted pool cut to the factor's own ``n`` and ``nnz``."""
    return col_ptr[:n + 1], rows[:nnz], vals[:nnz], D[:n]


def _finalize_factor(g: Graph, final: EngineState, col_base: jnp.ndarray,
                     *, n_phantom: int = 0, stats: dict) -> ACFactor:
    """Compact the engine pool on device and wrap it as an ``ACFactor``.

    Shared by the single-graph and batched paths; in the padded batched
    case ``final`` carries ``n_phantom`` pre-eliminated phantom vertices
    whose columns are empty — everything past position ``g.n`` is sliced
    away (phantom writes land at pool offsets ≥ nnz, never below).
    """
    n = g.n
    eliminated = int(final.n_elim) - n_phantom
    if eliminated != n:
        raise RuntimeError(
            f"engine stalled: {eliminated}/{n} eliminated "
            f"(overflow={int(final.overflow)})")
    rows_c, vals_c, col_ptr_d = _compact_pool(
        final.pool_row, final.pool_val, final.col_fill, col_base)
    nnz = int(col_ptr_d[n])
    col_ptr_g, rows_dev, vals_dev, D_dev = _factor_slices(
        col_ptr_d, rows_c, vals_c, final.D, n=n, nnz=nnz)
    dev = DeviceFactor(col_ptr=col_ptr_g, rows=rows_dev, vals=vals_dev,
                       D=D_dev)
    return ACFactor(n=n, col_ptr=np.asarray(col_ptr_g).astype(np.int64),
                    rows=np.asarray(rows_dev), vals=np.asarray(vals_dev),
                    D=np.asarray(D_dev), stats=stats, device=dev)


def _next_strict_slack(g: Graph, slack: int, overflow: int) -> int:
    """The slack of a strict retry after ``overflow`` dropped edges.  A
    slab holds live edges only and the graph never holds more than
    ``m``, so at ``fill_slack ≥ m`` no edge can be dropped."""
    if slack >= max(g.m, 1):
        raise RuntimeError(
            f"strict factorization dropped {overflow} sampled edges at "
            f"fill_slack={slack} >= m={g.m}, where no slab can overflow")
    return slack * 2


def factorize_wavefront(g: Graph, key: jax.Array, *, chunk: int = 64,
                        fill_slack: int = 32, strict: bool = True,
                        dtype=np.float32) -> ACFactor:
    """Parallel ParAC factorization.  Returns the same ``ACFactor`` as the
    sequential oracle (bit-identical for the same key when no overflow).

    ``strict`` re-runs at doubled ``fill_slack`` until no sampled edge is
    dropped, so a strict factor always has ``overflow == 0``; without it
    one run is made and its drops are counted in ``stats``."""
    n = g.n
    slack = fill_slack
    while True:
        with span("construct/pool"):
            (pool_row, pool_val, fill, dep, col_base, cap, P, dmax) = \
                _build_pool(g, slack, dtype)
        with span("construct/eliminate"):
            final = _run_engine(
                jnp.asarray(pool_row), jnp.asarray(pool_val),
                jnp.asarray(fill), jnp.asarray(dep), jnp.asarray(col_base),
                jnp.asarray(cap), key, dmax=dmax,
                chunk=min(chunk, max(n, 1)))
            ovf = int(final.overflow)
            rounds = int(final.n_rounds)
        if ovf == 0 or not strict:
            break
        slack = _next_strict_slack(g, slack, ovf)
    stats = dict(rounds=rounds, overflow=ovf,
                 chunk=chunk, fill_slack=slack, pool_size=P, dmax=dmax)
    with span("construct/finalize"):
        return _finalize_factor(g, final, jnp.asarray(col_base),
                                stats=stats)


# ---------------------------------------------------------------------------
# Batched fleet factorization
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def _pad_np(x: np.ndarray, size: int, fill) -> np.ndarray:
    if x.shape[0] == size:
        return x
    return np.concatenate([x, np.full(size - x.shape[0], fill, x.dtype)])


def factorize_batched(gs: Sequence[Graph], keys, *, chunk: int = 64,
                      fill_slack: int = 32, strict: bool = True,
                      dtype=np.float32,
                      bucket: bool = True, with_schedules: bool = False,
                      device: Optional[jax.Device] = None):
    """Factor a fleet of Laplacians concurrently in one XLA program.

    Pools are padded to a common shape bucket (powers of two when
    ``bucket`` — bounds jit recompiles across fleets) and the wavefront
    ``while_loop`` runs under ``vmap``.  Padding never changes a factor:
    phantom vertices start eliminated, phantom pool slots belong to
    zero-capacity columns, and the per-column math is padding-width
    independent (``column_math``), so each returned ``ACFactor`` is
    bit-identical to ``factorize_wavefront(g, key, ...)``.

    Overflow is handled per graph: converged graphs keep their factor
    while the overflowing subset re-runs at doubled slack (masked
    re-runs), mirroring the single-graph strict retry loop — a strict
    factor never carries ``overflow > 0``.

    With ``with_schedules`` the fleet's triangular level schedules are
    also derived in one vmapped pass (``trisolve.build_schedules_batched``
    over the padded device factors) and the call returns
    ``(factors, schedules)`` — the complete factor→solve admission
    payload in two batched XLA programs total.

    ``device`` targets the whole construction (wavefront engine,
    compaction and schedule derivation) at a specific accelerator —
    a dedicated factor replica runs here while serving replicas' solve
    programs run undisturbed on theirs.  Outputs stay uncommitted, so
    adopting them onto a serving device is one transfer at admission.
    """
    if device is not None:
        with jax.default_device(device):
            return factorize_batched(
                gs, keys, chunk=chunk, fill_slack=fill_slack,
                strict=strict, dtype=dtype, bucket=bucket,
                with_schedules=with_schedules)
    gs = list(gs)
    B = len(gs)
    if not isinstance(keys, jax.Array):
        keys = jnp.stack(list(keys))
    if keys.shape[0] != B:
        raise ValueError(f"got {B} graphs but {keys.shape[0]} keys")
    if B == 0:
        return ([], []) if with_schedules else []

    slacks = [fill_slack] * B
    results: List[Optional[ACFactor]] = [None] * B
    pending = list(range(B))
    while pending:
        with span("construct/pool"):
            built = {i: _build_pool(gs[i], slacks[i], dtype)
                     for i in pending}
        n_pad = max(max(gs[i].n for i in pending), 1)
        P_pad = max(max(built[i][6] for i in pending), 1)
        dmax_pad = max(built[i][7] for i in pending)
        if bucket:
            n_pad = _next_pow2(n_pad)
            P_pad = _next_pow2(P_pad)
            dmax_pad = _next_pow2(dmax_pad)
        chunk_eff = min(chunk, n_pad)

        PR, PV, CF, DP, CB, CP, E0 = [], [], [], [], [], [], []
        for i in pending:
            pool_row, pool_val, fill, dep, col_base, cap, P, _ = built[i]
            n = gs[i].n
            PR.append(_pad_np(pool_row, P_pad, INVALID_ID))
            PV.append(_pad_np(pool_val, P_pad, 0))
            CF.append(_pad_np(fill, n_pad, 0))
            DP.append(_pad_np(dep, n_pad, 0))
            CB.append(_pad_np(col_base, n_pad + 1, col_base[-1]))
            CP.append(_pad_np(cap, n_pad, 0))
            elim0 = np.zeros(n_pad, bool)
            elim0[n:] = True
            E0.append(elim0)
        with span("construct/eliminate"):
            out = _run_engine_batched(
                jnp.asarray(np.stack(PR)), jnp.asarray(np.stack(PV)),
                jnp.asarray(np.stack(CF)), jnp.asarray(np.stack(DP)),
                jnp.asarray(np.stack(CB)), jnp.asarray(np.stack(CP)),
                jnp.asarray(np.stack(E0)),
                jnp.stack([keys[i] for i in pending]),
                dmax=dmax_pad, chunk=chunk_eff)
            overflow = np.asarray(out.overflow)
            rounds = np.asarray(out.n_rounds)

        retry = []
        for bi, i in enumerate(pending):
            ovf = int(overflow[bi])
            if ovf == 0 or not strict:
                stats = dict(rounds=int(rounds[bi]), overflow=ovf,
                             chunk=chunk, fill_slack=slacks[i],
                             pool_size=int(built[i][6]),
                             dmax=int(built[i][7]), batched=True,
                             batch_size=len(pending), n_pad=n_pad,
                             P_pad=P_pad, dmax_pad=dmax_pad)
                final_i = jax.tree_util.tree_map(lambda x, bi=bi: x[bi],
                                                 out)
                with span("construct/finalize"):
                    results[i] = _finalize_factor(
                        gs[i], final_i, jnp.asarray(CB[bi]),
                        n_phantom=n_pad - gs[i].n, stats=stats)
            else:
                slacks[i] = _next_strict_slack(gs[i], slacks[i], ovf)
                retry.append(i)
        pending = retry
    if not with_schedules:
        return results
    from .trisolve import build_schedules_batched
    with span("construct/schedules"):
        return results, build_schedules_batched(
            [f.device for f in results])

"""Ahead-of-time compiles of the served path for a TPU v5e.

The TPU compiler is installed with jaxlib and compiles for a chip that
is described, not attached, so these tests run on a CPU-only machine and
catch what interpret-mode tests cannot: a kernel the chip's compiler
refuses, a tiling rule broken, a program that does not fit.  Shapes are
those of the two largest ``graphs.SUITE_LARGE`` graphs the chip smoke
serves (``grid3d_contrast_32``: n = 32,768; ``grid2d_256``: n = 65,536),
with the 128-lane panel width ``pad_k`` gives native runs.  A compile
that passes is not a chip run: nothing here executes.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and a
decision made at import would give pytest-xdist workers different tests.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.parac import _run_engine
from repro.core.pcg import FleetArrays, FleetPCGState, pcg_fleet_step
from repro.kernels.ops import ell_spmv_fleet, trisolve_fleet

LANES = 8          # SolveEngine's default slot count
K = 128            # pad_k under native lowering
# (name, n_pad, m_pad): pow2 buckets of the SUITE_LARGE graphs' n and m
GRAPHS = [("grid3d_contrast_32", 32768, 131072),
          ("grid2d_256", 65536, 131072)]
LEVELS = 256       # static level ceiling of a factor-kind fleet
# fwd/bwd rows per trisolve sweep (PackedSchedule.sweep_width) of the
# nnz-sorted graphs' ac factors: the power of two at or above a level's
# mean row count
WIDTHS = {"grid3d_contrast_32": (256, 256), "grid2d_256": (256, 256)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fleet_specs(sharding, n_pad, m_pad, rows=2):
    i32, f32 = jnp.int32, jnp.float32
    s = partial(_spec, sharding)
    return FleetArrays(
        src=s((rows, m_pad), i32), dst=s((rows, m_pad), i32),
        w=s((rows, m_pad), f32),
        fcols=s((rows, n_pad, K), i32), fvals=s((rows, n_pad, K), f32),
        flevel=s((rows, n_pad), i32),
        bcols=s((rows, n_pad, K), i32), bvals=s((rows, n_pad, K), f32),
        blevel=s((rows, n_pad), i32),
        dinv=s((rows, n_pad), f32), nvalid=s((rows,), i32),
        fnlv=s((rows,), i32), bnlv=s((rows,), i32),
        forder=s((rows, n_pad), i32), fext=s((rows, n_pad), i32),
        fgend=s((rows, n_pad), i32), fptr=s((rows, LEVELS + 1), i32),
        border=s((rows, n_pad), i32), bext=s((rows, n_pad), i32),
        bgend=s((rows, n_pad), i32), bptr=s((rows, LEVELS + 1), i32))


def _state_specs(sharding, n_pad):
    s = partial(_spec, sharding)
    blk = s((LANES, n_pad), jnp.float32)
    lane_f, lane_i = s((LANES,), jnp.float32), s((LANES,), jnp.int32)
    return FleetPCGState(X=blk, R=blk, Z=blk, P=blk, rz=lane_f, it=lane_i,
                         active=s((LANES,), jnp.bool_), bnorm=lane_f,
                         fidx=lane_i, tol=lane_f, maxiter=lane_i)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("name,n_pad,m_pad", GRAPHS)
def test_ell_spmv_fleet_compiles(one_chip, name, n_pad, m_pad):
    c = _compile(ell_spmv_fleet,
                 _spec(one_chip, (LANES, n_pad, K), jnp.int32),
                 _spec(one_chip, (LANES, n_pad, K), jnp.float32),
                 _spec(one_chip, (LANES, n_pad), jnp.float32))
    # the served SpMV is the XLA gather form, not a Pallas kernel
    assert "tpu_custom_call" not in c.as_text()


@pytest.mark.parametrize("name,n_pad,m_pad", GRAPHS)
def test_trisolve_fleet_compiles(one_chip, name, n_pad, m_pad):
    s = partial(_spec, one_chip)

    def solve(cols, vals, level_of, y, lane_levels, plan):
        return trisolve_fleet(cols, vals, level_of, y, n_levels=LEVELS,
                              lane_levels=lane_levels,
                              width=WIDTHS[name][0], plan=plan)

    lane_rows = s((LANES, n_pad), jnp.int32)
    c = _compile(solve, s((LANES, n_pad, K), jnp.int32),
                 s((LANES, n_pad, K), jnp.float32),
                 lane_rows, s((LANES, n_pad), jnp.float32),
                 s((LANES,), jnp.int32),
                 (lane_rows, lane_rows, lane_rows,
                  s((LANES, LEVELS + 1), jnp.int32)))
    # one sweep program per panel class (8 … K slots)
    assert c.as_text().count("conditional") >= 1


@pytest.mark.parametrize("kind", ["factor", "spmv"])
@pytest.mark.parametrize("name,n_pad,m_pad", GRAPHS)
def test_pcg_fleet_step_compiles(one_chip, name, n_pad, m_pad, kind):
    levels, (fw, bw) = (LEVELS, WIDTHS[name]) if kind == "factor" \
        else (1, (1, 1))
    step = partial(pcg_fleet_step, k=8, f_levels=levels, b_levels=levels,
                   kind=kind, f_width=fw, b_width=bw)
    c = _compile(step, _fleet_specs(one_chip, n_pad, m_pad),
                 _state_specs(one_chip, n_pad))
    assert c.memory_analysis() is not None


@pytest.mark.parametrize("name,n,m,slack", [
    ("grid3d_contrast_32", 32768, 95232, 32),
    ("grid2d_256", 65536, 130560, 32)])
def test_run_engine_compiles(one_chip, name, n, m, slack):
    # _build_pool's layout: P = m + n·slack slots; dmax = max cap, which
    # for these graphs (owned degree ≤ 3 / 2) is slack + a few
    P = m + n * slack
    dmax = slack + 8
    s = partial(_spec, one_chip)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=one_chip)
    c = _run_engine.lower(
        s((P,), jnp.int32), s((P,), jnp.float32), s((n,), jnp.int32),
        s((n,), jnp.int32), s((n + 1,), jnp.int32), s((n,), jnp.int32),
        key, dmax=dmax, chunk=64).compile()
    assert np.isfinite(c.memory_analysis().temp_size_in_bytes)

"""Pallas flash attention (forward) — the fusion that closes the
S²-logits memory gap quantified in EXPERIMENTS.md §Perf cell B: the
(S×S) score tile never leaves VMEM, so HBM traffic drops from
O(S²) to O(S·d) per head.

Blocked online-softmax (Dao et al.): grid over (batch·heads, q-tiles);
the kernel keeps a q tile plus running (max, denom, acc) registers and
loops over KV tiles with `jax.lax.fori_loop`.  Causal masking skips
fully-masked KV tiles via the loop bound.

Interpret-mode validated against the pure-jnp oracle
(`ref.flash_attention_ref`); on TPU hardware the same call lowers with
MXU dots and VMEM tiling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_k, causal,
            q_tile):
    q = q_ref[...]                      # (Tq, d)
    Tq, d = q.shape
    S = k_ref.shape[0]
    qi = pl.program_id(1)
    q0 = qi * q_tile                    # global row offset of this q tile

    nblocks = S // block_k
    if causal:
        # last KV tile that intersects the causal triangle
        nblocks = jnp.minimum(nblocks,
                              (q0 + Tq + block_k - 1) // block_k)

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(kb * block_k, block_k), :]    # (Tk, d)
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q.astype(jnp.float32) * scale, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())))              # (Tq, Tk)
        if causal:
            rows = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((Tq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Tq,), jnp.float32)
    a0 = jnp.zeros((Tq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nblocks, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_tile: int = 128,
                    block_k: int = 128, interpret: bool = True):
    """q,k,v: [B, H, S, d] (same S for q and kv).  Returns [B, H, S, d].

    S must divide by q_tile and block_k (pad outside if needed)."""
    B, H, S, d = q.shape
    assert S % q_tile == 0 and S % block_k == 0, (S, q_tile, block_k)
    scale = 1.0 / math.sqrt(d)
    qf = q.reshape(B * H, S, d)
    kf = k.reshape(B * H, S, d)
    vf = v.reshape(B * H, S, d)
    grid = (B * H, S // q_tile)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_k=block_k,
                          causal=causal, q_tile=q_tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, q_tile, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_tile, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, d)

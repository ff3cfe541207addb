"""Causal flash attention (prefill) with masked-block skipping.

REMOP framing: K/V stream HBM->VMEM in (bq, bk)-blocked rounds with an online
softmax in VMEM scratch; block sizes are the buffer partition (bigger blocks
=> fewer DMA rounds => more VMEM), and *fully-masked* causal blocks are
skipped with `pl.when` — removing ~half of both the D term (those blocks'
DMAs are still issued by the grid, but no compute) and the compute term that
the pure-jnp chunked oracle pays.

Grid: (batch, q_head, q_block, kv_block) with kv innermost/sequential so the
(m, l, acc) scratch accumulates per q_block and Pallas double-buffers the
next KV block's DMA behind the current block's compute (§IV-E).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, n_kv: int, q_offset: int):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal block skip: block (i, j) is fully masked iff its smallest q pos
    # is below its smallest kv pos.
    q_base = i * bq + q_offset
    k_base = j * bk

    @pl.when(q_base + bq - 1 >= k_base)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T) / math.sqrt(q.shape[-1])  # [bq, bk]
        q_pos = q_base + jax.lax.iota(jnp.int32, bq)
        k_pos = k_base + jax.lax.iota(jnp.int32, bk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, bq: int = 128, bk: int = 128,
                    interpret: bool = True):
    """q: [B, H, S, hd]; k/v: [B, KV, T, hd]; causal with offset T - S."""
    b, h, s, hd = q.shape
    kv = k.shape[1]
    t = k.shape[2]
    g = h // kv
    assert s % bq == 0 and t % bk == 0, (s, t, bq, bk)
    grid = (b, h, s // bq, t // bk)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, n_kv=t // bk,
                          q_offset=t - s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, hh, ii, jj: (bb, hh // g, jj, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bb, hh, ii, jj: (bb, hh // g, jj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bb, hh, ii, jj: (bb, hh, ii, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)

"""Paged decode attention (flash-decoding) — KV paging as REMOP rounds.

The KV cache lives in HBM ("remote memory" relative to VMEM); each grid step
DMAs one page of K and V into VMEM — one transfer round — and folds it into
an online softmax held in VMEM scratch.  Page size comes from
``core.planner.plan_kv_pages``: L = D + tau_dma * C over page candidates,
trading tail over-fetch (D) against round count (C), exactly the paper's
Eq. (2) with DMA constants.

Grid: (batch, kv_head, page) with the page axis innermost/sequential so the
scratch (m, l, acc) accumulates across pages and Pallas double-buffers the
next page's DMA behind the current page's compute (§IV-E prefetch buffer).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page: int, n_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # [G, hd]
    k = k_ref[0, :, 0, :]  # [page, hd]
    v = v_ref[0, :, 0, :]
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.dot(q.astype(jnp.float32), k.astype(jnp.float32).T) * scale  # [G, page]
    positions = p * page + jax.lax.iota(jnp.int32, page)
    valid = positions < len_ref[b]
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(pexp, v.astype(jnp.float32))
    m_ref[...] = m_new

    @pl.when(p == n_pages - 1)
    def _flush():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_cache, v_cache, lengths, page: int = 128,
                    interpret: bool = True):
    """q: [B, KV, G, hd]; k/v_cache: [B, S, KV, hd]; lengths: [B] int32."""
    b, kv, g, hd = q.shape
    s = k_cache.shape[1]
    assert s % page == 0, (s, page)
    n_pages = s // page
    grid = (b, kv, n_pages)
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page=page, n_pages=n_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, hd), lambda bb, hh, pp, len_ref: (bb, hh, 0, 0)),
                pl.BlockSpec((1, page, 1, hd), lambda bb, hh, pp, len_ref: (bb, pp, hh, 0)),
                pl.BlockSpec((1, page, 1, hd), lambda bb, hh, pp, len_ref: (bb, pp, hh, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd), lambda bb, hh, pp, len_ref: (bb, hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)

"""REMOP blocked matmul — the BNLJ analogue as a Pallas TPU kernel.

The loop nest IS Algorithm 1: the A row-panel is the pinned outer block
(held across the inner sweep), B column-panels stream through VMEM as the
inner relation, and the (bm, bn) accumulator is the output region flushed
once per (i, j) tile.  Tile shapes come from ``core.planner.plan_matmul_tiles``
which minimizes L = D + tau_dma * C over hardware-legal shapes — the same
algebra as the paper's p_R:p_S split with tau calibrated to DMA issue
overhead instead of network RTT.

Grid order (i, j, k): k innermost so the f32 accumulator lives in VMEM
scratch across the K sweep; Pallas's sequential-grid pipelining provides the
§IV-E prefetch double buffer (block (i, j, k+1) DMAs overlap compute on
(i, j, k)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, out_ref, acc_ref, *, n_k: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k_idx == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    bm: int,
    bn: int,
    bk: int,
    out_dtype=None,
    interpret: bool = True,
) -> jnp.ndarray:
    """Tiled matmul with explicit BlockSpec VMEM tiling.

    a: [M, K]; b: [K, N].  M % bm == K % bk == N % bn == 0 (caller pads).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    out_dtype = out_dtype or a.dtype
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)

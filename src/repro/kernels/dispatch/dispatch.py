"""MoE dispatch gather — the EHJ radix-partition analogue in Pallas.

After the merge-sort kernel orders assignments by expert (the paper's radix
partitioning), moving token rows into per-expert contiguous buffers is a pure
gather.  The kernel below is that gather: the source table stays in HBM
(``memory_space=pl.ANY``), each grid step takes ``GATHER_BLOCK`` row indices
as an SMEM block, starts one row DMA HBM->VMEM per index into its aligned
output block, and waits for all of them — one transfer round per block, with
Pallas writing the finished block back while the next one gathers (§IV-E
prefetch).

Layout: a row DMA must move whole 128-lane lines, so rows narrower than a
multiple of 128 lanes are zero-padded to one before the call and the padding
is sliced off after it.  Indices are clamped into ``[0, rows)`` (XLA's own
gather semantics), so no DMA can leave the table.

Staging-pool sizing (how many rows per all-to-all round when experts live on
other chips) comes from ``core.planner.plan_dispatch`` (Property 6 waterfill).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows per grid step: XLA lays a 1-D int32 index vector out in 1024-element
# tiles, and an SMEM block must match that tiling.
GATHER_BLOCK = 1024


def _gather_kernel(idx_ref, x_hbm, o_ref, sem):
    def copy(r):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(idx_ref[r], 1)], o_ref.at[pl.ds(r, 1)], sem
        )

    def start(r, carry):
        copy(r).start()
        return carry

    def wait(r, carry):
        copy(r).wait()
        return carry

    jax.lax.fori_loop(0, GATHER_BLOCK, start, 0)
    jax.lax.fori_loop(0, GATHER_BLOCK, wait, 0)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def gather_rows(x: jnp.ndarray, idx: jnp.ndarray,
                interpret: bool = True) -> jnp.ndarray:
    """out[i] = x[idx[i]] by one row DMA per index, ``GATHER_BLOCK`` per step.

    ``x`` is a 2-D table of 32-bit values; out-of-range indices clamp.
    """
    t, d = x.shape
    n = idx.shape[0]
    lanes = _round_up(d, LANES)
    n_pad = _round_up(max(n, 1), GATHER_BLOCK)
    xp = jnp.pad(x, ((0, 0), (0, lanes - d)))
    ip = jnp.pad(jnp.clip(idx.astype(jnp.int32), 0, t - 1), (0, n_pad - n))
    out = pl.pallas_call(
        _gather_kernel,
        grid=(n_pad // GATHER_BLOCK,),
        in_specs=[
            pl.BlockSpec((GATHER_BLOCK,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((GATHER_BLOCK, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, lanes), x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name="gather_rows",
    )(ip, xp)
    return out[:n, :d]

"""SSD inter-chunk state scan — the sequential hot-spot of Mamba-2 as Pallas.

In the chunked SSD algorithm the intra-chunk work is dense matmuls (MXU);
what remains serial is the [H, P, N] state passed between chunks:

    carry_{c+1} = carry_c * decay_c + state_c

The REMOP shape: the carry stays RESIDENT in VMEM scratch across the whole
grid (the pinned outer block) while per-chunk states stream HBM->VMEM one
round each, with Pallas double-buffering chunk c+1's DMA behind chunk c's
update (§IV-E).  A pure-jnp lax.scan instead round-trips the carry through
HBM every chunk — 2x the rounds on the carried state.

Grid: (batch, chunk) with chunk innermost/sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_scan_kernel(states_ref, decay_ref, prev_ref, final_ref, carry_ref,
                     *, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    carry = carry_ref[...]
    prev_ref[0, 0] = carry.astype(prev_ref.dtype)  # exclusive output
    decay = decay_ref[0, 0]  # [H]
    state = states_ref[0, 0].astype(jnp.float32)  # [H, P, N]
    carry_ref[...] = carry * decay[:, None, None].astype(jnp.float32) + state

    @pl.when(c == n_chunks - 1)
    def _final():
        final_ref[0] = carry_ref[...].astype(final_ref.dtype)


def ssd_scan(states: jnp.ndarray, decays: jnp.ndarray,
             interpret: bool = True):
    """states: [B, NC, H, P, N]; decays: [B, NC, H] ->
    (prev_states [B, NC, H, P, N], final [B, H, P, N])."""
    b, nc, h, p, n = states.shape
    grid = (b, nc)
    prev, final = pl.pallas_call(
        functools.partial(_ssd_scan_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, h, p, n), lambda bb, cc: (bb, cc, 0, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda bb, cc: (bb, cc, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, h, p, n), lambda bb, cc: (bb, cc, 0, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda bb, cc: (bb, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(states.shape, states.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), states.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(states, decays)
    return prev, final

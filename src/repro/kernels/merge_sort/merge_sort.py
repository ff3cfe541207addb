"""Blocked bitonic merge sort — the EMS analogue as Pallas TPU kernels.

Structure mirrors external merge sort (§III-B):
  * run formation: each VMEM-sized block is sorted in-core by a bitonic
    network (`sort_blocks`) — one grid step = one HBM->VMEM->HBM round trip;
  * merge passes: adjacent sorted runs are merged pairwise by a bitonic
    merge ladder (`merge_pass`) until one run remains.

Hardware adaptation: the paper's tournament tree is data-dependent and does
not vectorize on the VPU; the bitonic network has a fixed dataflow built from
rotations + min/max only (no gathers).  A logical fan-in-k merge pass is
log2(k) pairwise ladders; ``core.planner.plan_sort`` picks k from Table IV
with tau calibrated to DMA overhead, trading pass count (volume D) against
per-pass rounds (C) exactly as the paper does.

Layout.  Every operand is a lane-dense ``(rows, 128)`` array of 32-bit
values: element ``e`` lives at row ``e // 128``, lane ``e % 128``, and a
block is ``(block // 128, 128)`` with ``block`` a power of two of at least
one ``(8, 128)`` tile.  A compare-exchange at distance ``2**j`` finds its
partner with ``pltpu.roll`` along lanes (``2**j < 128``) or along rows
(``2**j >= 128``) and selects with ``iota`` masks.  Runs are formed in
alternating directions (the direction of element ``e`` at stage ``k`` is bit
``k`` of its global index), so two adjacent runs are already bitonic and a
merge never reverses one of them.

VMEM split.  Only stages whose distance is inside one block run in a kernel.
The stages of a merge whose distance is a block or more are plain XLA
min/max between the two halves of each group (`_exchange_halves`), outside
any kernel.  A kernel therefore holds one block per operand however long the
runs grow: with the default ``MAX_BLOCK`` of 2**14 keys that is 64 KiB per
operand, double-buffered in and out — far below v5e's 16 MiB default scoped
VMEM, so no kernel raises ``vmem_limit_bytes``.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_BLOCK = 8 * LANES  # one (8, 128) 32-bit tile
MAX_BLOCK = 1 << 14  # keys per in-VMEM block (128 rows)

Cols = Tuple[jax.Array, ...]  # (keys, *payloads), each (rows, 128)


def _exchange(cols: Cols, g, j: int, k: int) -> Cols:
    """One in-block compare-exchange at distance 2**j of merge stage k.

    ``g`` holds each element's global index.  The element whose bit j is
    clear keeps the minimum when bit k of its index is clear (ascending run)
    and the maximum otherwise; its partner keeps the other one.  Equal keys
    never swap, so payloads stay paired with their keys.
    """
    d = 1 << j
    if d < LANES:
        axis, size, s = 1, LANES, d
    else:
        axis, size, s = 0, cols[0].shape[0], d // LANES
    lower = (g & d) == 0
    want_min = (((g >> j) ^ (g >> k)) & 1) == 0  # bit j == bit k

    def partner(x):
        # roll by size - s brings x[i + s] to i; roll by s brings x[i - s].
        return jnp.where(lower, pltpu.roll(x, size - s, axis),
                         pltpu.roll(x, s, axis))

    keys = cols[0]
    pk = partner(keys)
    # Boolean logic, not a select between masks: Mosaic has no i1 select.
    take = (want_min & (pk < keys)) | (~want_min & (pk > keys))
    return (jnp.where(take, pk, keys),) + tuple(
        jnp.where(take, partner(c), c) for c in cols[1:]
    )


def _bitonic_kernel(*refs, stages: Sequence[Tuple[int, int]]):
    n = len(refs) // 2
    cols = tuple(r[...] for r in refs[:n])
    rows = cols[0].shape[0]
    block = rows * LANES
    g = (pl.program_id(0) * block
         + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0) * LANES
         + jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    for k, j in stages:
        cols = _exchange(cols, g, j, k)
    for r, c in zip(refs[n:], cols):
        r[...] = c


def _blocked_call(cols: Cols, block: int, stages, interpret: bool) -> Cols:
    rows = block // LANES
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        lambda *refs: _bitonic_kernel(*refs, stages=tuple(stages)),
        grid=(cols[0].shape[0] // rows,),
        in_specs=[spec] * len(cols),
        out_specs=[spec] * len(cols),
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in cols],
        interpret=interpret,
        name="bitonic_block",
    )(*cols)
    return tuple(out)


def _log2(n: int) -> int:
    return n.bit_length() - 1


def sort_blocks(cols: Cols, block: int, interpret: bool = True) -> Cols:
    """Sort each ``block``-key run in-core, in alternating directions.

    ``cols`` are ``(rows, 128)`` arrays, keys first; ``block`` is a power of
    two with ``MIN_BLOCK <= block`` and ``rows * 128 % block == 0``.  Run i
    comes out ascending when i is even and descending when i is odd.
    """
    assert block >= MIN_BLOCK and block & (block - 1) == 0
    assert (cols[0].shape[0] * LANES) % block == 0
    log_b = _log2(block)
    stages = [(k, j) for k in range(1, log_b + 1) for j in range(k - 1, -1, -1)]
    return _blocked_call(cols, block, stages, interpret)


def _exchange_halves(cols: Cols, j: int, k: int) -> Cols:
    """Compare-exchange at distance 2**j >= one block, as plain XLA ops."""
    rows = cols[0].shape[0]
    s = (1 << j) // LANES  # row distance
    shaped = [c.reshape(rows // (2 * s), 2, s, LANES) for c in cols]
    a, b = shaped[0][:, 0], shaped[0][:, 1]
    group = jnp.arange(rows // (2 * s), dtype=jnp.int32)
    desc = ((group >> (k - j - 1)) & 1).astype(bool)[:, None, None]
    swap = jnp.where(desc, a < b, a > b)
    out = []
    for c in shaped:
        lo, hi = c[:, 0], c[:, 1]
        out.append(jnp.stack([jnp.where(swap, hi, lo), jnp.where(swap, lo, hi)],
                             axis=1).reshape(rows, LANES))
    return tuple(out)


def merge_pass(cols: Cols, run: int, block: int, interpret: bool = True) -> Cols:
    """Merge adjacent bitonic pairs of ``run``-key runs into ``2*run`` runs.

    The output runs alternate direction like `sort_blocks`' (the last pass,
    with one run left, is ascending).  Distances ``>= block`` run as XLA
    stages between halves; the ``log2(block)`` in-block stages run in one
    kernel over ``block``-key tiles.
    """
    assert run >= block and (cols[0].shape[0] * LANES) % (2 * run) == 0
    k = _log2(2 * run)
    log_b = _log2(block)
    for j in range(k - 1, log_b - 1, -1):
        cols = _exchange_halves(cols, j, k)
    stages = [(k, j) for j in range(log_b - 1, -1, -1)]
    return _blocked_call(cols, block, stages, interpret)

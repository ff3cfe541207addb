"""Jitted wrapper: full external merge sort with REMOP-planned runs/fan-in."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.planner import plan_sort
from repro.kernels.merge_sort.merge_sort import (
    LANES, MAX_BLOCK, MIN_BLOCK, merge_pass, sort_blocks,
)
from repro.kernels.runtime import resolve_interpret


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 << max(0, (n - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("run_items", "interpret"))
def remop_sort(keys: jnp.ndarray, values: jnp.ndarray | None = None,
               run_items: int | None = None, interpret: bool | None = None):
    """Sort (keys[, values]) ascending via blocked bitonic merge sort.

    Returns ``(sorted_keys, values_in_key_order)``; the second item is
    ``None`` when no ``values`` were given (the kernels then move keys only).
    `run_items` (power of two) is the in-core run size; defaults to the
    REMOP sort plan's run for the key dtype, and is clamped to
    ``[MIN_BLOCK, MAX_BLOCK]`` keys, one VMEM block.  ``interpret=None``
    auto-detects the Pallas mode (compiled on TPU/GPU, interpreter on CPU).
    Keys and values must be 32-bit (the kernels' lane-dense tiles).
    """
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    if run_items is None:
        plan = plan_sort(n, item_bytes=keys.dtype.itemsize + 4)
        run_items = next_pow2(plan.run_items)
    n_pad = max(next_pow2(n), MIN_BLOCK)
    block = min(max(next_pow2(run_items), MIN_BLOCK), MAX_BLOCK, n_pad)
    if keys.dtype.kind == "f":
        sentinel = jnp.array(jnp.inf, keys.dtype)
    else:
        sentinel = jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
    cols = [jnp.full((n_pad,), sentinel, keys.dtype).at[:n].set(keys)]
    if values is not None:
        cols.append(jnp.zeros((n_pad,), values.dtype).at[:n].set(values))
    cols = sort_blocks(tuple(c.reshape(-1, LANES) for c in cols), block,
                       interpret=interpret)
    run = block
    while run < n_pad:
        cols = merge_pass(cols, run, block, interpret=interpret)
        run *= 2
    out = [c.reshape(-1)[:n] for c in cols]
    return out[0], (out[1] if values is not None else None)


def argsort_by_key(keys: jnp.ndarray, interpret: bool | None = None,
                   max_key: int | None = None) -> jnp.ndarray:
    """Stable argsort via unique composite keys (key-major, index-minor).

    Requires ``max(keys) * n + n < 2**31`` (the composite is built in int32).
    The precondition is checked at trace time from static bounds: ``max_key``
    when given (a static promise about the key range — e.g. ``n_experts - 1``
    for MoE expert ids), else the key dtype's maximum.  A violated bound
    raises ``ValueError`` instead of silently overflowing into a wrong
    permutation.
    """
    n = int(keys.shape[0])
    if keys.dtype.kind not in "iu":
        raise ValueError(
            f"argsort_by_key needs integer keys, got dtype {keys.dtype}"
        )
    bound = int(jnp.iinfo(keys.dtype).max) if max_key is None else int(max_key)
    if bound < 0:
        raise ValueError(f"max_key must be >= 0, got {max_key}")
    if n and bound * n + n >= 2**31:
        raise ValueError(
            f"argsort_by_key composite overflows int32: "
            f"max_key({bound}) * n({n}) + n >= 2**31 — pass a tighter "
            f"static max_key= bound for the actual key range"
        )
    composite = keys.astype(jnp.int32) * jnp.int32(n) + jnp.arange(n, dtype=jnp.int32)
    _, idx = remop_sort(composite, jnp.arange(n, dtype=jnp.int32),
                        interpret=interpret)
    return idx

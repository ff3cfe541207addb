"""Mixture-of-Experts layer (top-k routing, capacity-based dispatch).

The dispatch is the EHJ analogue (DESIGN.md §3): tokens are radix-partitioned
across experts; tokens routed to experts on other chips are the "spilled
partitions" that must be staged and moved by all-to-all.  The staging-pool
sizing lives in ``core/planner.plan_dispatch`` and the TPU-native kernel in
``kernels/dispatch``; here the dense-math dispatch uses static capacity so the
layer shards cleanly under GSPMD (experts on the ``model``/EP axis).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_sharder
from repro.models.layers import init_mlp, mlp, truncated_normal

# MoE execution strategy:
#   "gspmd"        — batch-grouped dispatch under the SPMD partitioner
#                    (baseline; GSPMD re-gathers the expert dim around the
#                    dispatch scatter — measured in §Perf).
#   "ep_shard_map" — manual expert parallelism: each model-axis shard keeps
#                    its E/ep local experts, routes ALL local tokens against
#                    them (mask + local scatter), and partial outputs are
#                    psum-combined.  No expert-dim resharding ever happens;
#                    the cross-shard traffic is one activation-sized psum per
#                    layer — the EHJ "spilled partitions join locally, ship
#                    results once" schedule.
_MOE_IMPL = "gspmd"


def set_moe_impl(name: str) -> None:
    global _MOE_IMPL
    assert name in ("gspmd", "ep_shard_map")
    _MOE_IMPL = name


def init_moe(key, cfg: ModelConfig) -> Dict:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": {"w": truncated_normal(ks[0], (d, e), 1.0 / math.sqrt(d))},
        "experts": {
            "w_gate": truncated_normal(ks[1], (e, d, ff), 1.0 / math.sqrt(d)),
            "w_up": truncated_normal(ks[2], (e, d, ff), 1.0 / math.sqrt(d)),
            "w_down": truncated_normal(ks[3], (e, ff, d), 1.0 / math.sqrt(ff)),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared_experts * ff, "swiglu")
    return p


def topk_route(router_logits: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (weights [T,k], expert_ids [T,k], aux_loss scalar)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss: E * sum_e f_e * P_e.
    e = router_logits.shape[-1]
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=1), axis=0
    )
    p_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p_mean)
    return weights.astype(jnp.bfloat16), ids, aux


def moe_apply(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
              capacity_factor: float | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B,S,d] -> (y, aux_loss).  Batch-grouped static-capacity dispatch.

    Capacity is per sequence (GShard-style group-local dropping), so the
    dispatch scatter is batch-local: under pjit the batch dim stays on the
    data axis and experts on the model (EP) axis, the expert matmuls contract
    the unsharded d_ff dim, and the only cross-device movement is the
    expert_in/out resharding — the EHJ "spilled partition" all-to-all
    (DESIGN.md §3), whose staging budget core.planner.plan_dispatch sizes.
    """
    capacity_factor = capacity_factor or cfg.capacity_factor
    sharder = current_sharder()
    if (_MOE_IMPL == "ep_shard_map" and sharder is not None
            and "model" in sharder.axis_sizes
            and cfg.n_experts % sharder.axis_sizes["model"] == 0):
        return _moe_apply_ep(p, cfg, x, capacity_factor, sharder)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    # SP exit: gather the sequence locally (batch stays on the data axis) so
    # routing cumsums and the dispatch scatter are device-local — otherwise
    # GSPMD replicates the [B, S*k, E] position tensors across the mesh.
    x = constrain(x, ("batch", None, None))
    logits = x @ p["router"]["w"].astype(x.dtype)  # [B,S,E]
    logits = constrain(logits, ("batch", None, None))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)  # [B,S,k]
    weights = (weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
               ).astype(x.dtype)
    # Switch-style load-balance aux loss over the global batch.
    f_frac = jnp.mean(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=(0, 1, 2))
    aux = e * jnp.sum(f_frac * jnp.mean(probs, axis=(0, 1)))

    capacity = max(1, int(capacity_factor * s * k / e))
    a_r = s * k
    flat_ids = ids.reshape(b, a_r)  # token-major, choice-minor
    one_hot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)  # [B, A, E]
    pos = jnp.cumsum(one_hot, axis=1) - 1
    pos_in_e = jnp.take_along_axis(pos, flat_ids[..., None], axis=2)[..., 0]
    keep = pos_in_e < capacity
    safe_pos = jnp.where(keep, pos_in_e, capacity - 1)

    tok_idx = jnp.repeat(jnp.arange(s), k)  # [A], same for every row
    updates = jnp.where(keep[..., None], x[:, tok_idx, :], 0)
    updates = constrain(updates, ("batch", None, None))

    def scatter_row(ids_r, pos_r, upd_r):
        return jnp.zeros((e, capacity, d), x.dtype).at[ids_r, pos_r].add(upd_r)

    expert_in = jax.vmap(scatter_row)(flat_ids, safe_pos, updates)
    expert_in = constrain(expert_in, ("batch", "expert", None, None))

    w_g = p["experts"]["w_gate"].astype(x.dtype)
    w_u = p["experts"]["w_up"].astype(x.dtype)
    w_d = p["experts"]["w_down"].astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in, w_g))
    h = h * jnp.einsum("becd,edf->becf", expert_in, w_u)
    h = constrain(h, ("batch", "expert", None, None))
    expert_out = jnp.einsum("becf,efd->becd", h, w_d)
    expert_out = constrain(expert_out, ("batch", "expert", None, None))

    def gather_row(out_r, ids_r, pos_r):
        return out_r[ids_r, pos_r]

    gathered = jax.vmap(gather_row)(expert_out, flat_ids, safe_pos)  # [B,A,d]
    gathered = jnp.where(keep[..., None], gathered, 0)
    gathered = constrain(gathered, ("batch", None, None))
    # Combine: assignments are (token-major, choice-minor) => pure reshape.
    y = (gathered.reshape(b, s, k, d)
         * weights[..., None]).sum(axis=2)

    if "shared" in p:
        y = y + mlp(p["shared"], x, "swiglu")
    return y, aux.astype(jnp.float32)


def _route(x2d, router_w, k):
    """Shared routing math: returns (weights [T,k], ids [T,k], aux scalar)."""
    logits = x2d @ router_w.astype(x2d.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    weights = (weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
               ).astype(x2d.dtype)
    e = probs.shape[-1]
    f_frac = jnp.mean(jax.nn.one_hot(ids, e, dtype=jnp.float32), axis=(0, 1))
    aux = e * jnp.sum(f_frac * jnp.mean(probs, axis=0))
    return weights, ids, aux


def _moe_apply_ep(p: Dict, cfg: ModelConfig, x: jnp.ndarray,
                  capacity_factor: float, sharder) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Manual-EP MoE: local experts per model shard + psum combine."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    mesh = sharder.mesh
    ep = sharder.axis_sizes["model"]
    e_loc = e // ep
    capacity = max(1, int(capacity_factor * s * k / e))
    x = constrain(x, ("batch", None, None))  # SP exit; model-replicated

    w_g, w_u, w_d = (p["experts"]["w_gate"], p["experts"]["w_up"],
                     p["experts"]["w_down"])
    router_w = p["router"]["w"]

    batch_axes = tuple(a for a in ("pod", "data") if a in sharder.axis_sizes)

    def local(xb, rw, wgb, wub, wdb):
        # Full-manual: xb is this shard's [B_loc, S, d] batch slice (replicated
        # across model); expert weights are this shard's [e_loc, ...] slice.
        # (Partial-manual shard_map triggers an XLA-CPU crash in
        # AllReducePromotion via a copy-combiner all-reduce; full manual is
        # the mature path and costs nothing here.)
        my = jax.lax.axis_index("model")
        bb, ss, dd = xb.shape
        wgt, ids, aux = _route(xb.reshape(bb * ss, dd), rw, k)
        wgt = wgt.reshape(bb, ss * k)
        ids_loc = ids.reshape(bb, ss * k) - my * e_loc
        mask = (ids_loc >= 0) & (ids_loc < e_loc)
        safe_ids = jnp.where(mask, ids_loc, 0)
        one_hot = jax.nn.one_hot(safe_ids, e_loc, dtype=jnp.int32)
        one_hot = one_hot * mask[..., None].astype(jnp.int32)
        pos = jnp.cumsum(one_hot, axis=1) - 1
        pos_in = jnp.take_along_axis(pos, safe_ids[..., None], axis=2)[..., 0]
        keep = mask & (pos_in < capacity)
        safe_pos = jnp.where(keep, pos_in, capacity - 1)
        tok = jnp.repeat(jnp.arange(ss), k)
        upd = jnp.where(keep[..., None], xb[:, tok, :], 0)

        def scatter_row(ids_r, pos_r, upd_r):
            return jnp.zeros((e_loc, capacity, dd), xb.dtype).at[
                ids_r, pos_r].add(upd_r)

        expert_in = jax.vmap(scatter_row)(safe_ids, safe_pos, upd)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", expert_in,
                                   wgb.astype(xb.dtype)))
        h = h * jnp.einsum("becd,edf->becf", expert_in, wub.astype(xb.dtype))
        out = jnp.einsum("becf,efd->becd", h, wdb.astype(xb.dtype))

        def gather_row(out_r, ids_r, pos_r):
            return out_r[ids_r, pos_r]

        rows = jax.vmap(gather_row)(out, safe_ids, safe_pos)
        rows = jnp.where(keep[..., None], rows, 0) * wgt[..., None]
        y = rows.reshape(bb, ss, k, dd).sum(axis=2)
        # Return f32 from the manual region: XLA CPU's AllReducePromotion
        # pass crashes cloning the bf16 copy-combiner all-reduce that GSPMD
        # emits at the shard_map exit; f32 outputs sidestep the pass (and the
        # f32 psum avoids precision loss in the combine anyway).
        y = jax.lax.psum(y.astype(jnp.float32), "model")
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    y, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_axes if batch_axes else None, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(batch_axes if batch_axes else None, None, None), P()),
        check_vma=False,
    )(x, router_w, w_g, w_u, w_d)
    if batch_axes:
        aux = aux  # identical across batch shards (same formula per shard mean)
    y = y.astype(x.dtype)
    if "shared" in p:
        y = y + mlp(p["shared"], x, "swiglu")
    return y, aux.astype(jnp.float32)

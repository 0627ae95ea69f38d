"""shard_map MoE dispatch: the §Perf cell B "b3" design, validated.

Auto-SPMD resolves the token->expert-buffer scatter of `models/moe.py`
by all-gathering the full dispatch payload (54.8 GB/device/layer on the
moonshot train cell).  The communication-optimal dispatch is a single
all-to-all, which requires manual SPMD (shard_map):

  per data shard (T_loc tokens):
    1. route locally: stable-argsort the (T_loc * k) assignments by
       expert, position each within a fixed per-(shard, expert) capacity
       C_loc (drop beyond — same dropping semantics as the global path,
       applied per shard);
    2. build the local send buffer (E, C_loc, d);
    3. `lax.all_to_all` over the expert axis -> each shard receives
       (E/S, S * C_loc, d): ITS experts' tokens from every shard;
    4. expert FFN on local experts;
    5. reverse all_to_all, local combine with the gate weights.

  Traffic per step = send-buffer bytes = E * C_loc * d, i.e. the payload
  itself (~T*k*d/S per shard), vs the payload *all-gathered S times* in
  the auto-SPMD path — the ~500x in EXPERIMENTS.md §Perf cell B.

This module is the validated building block (tests/test_multidevice.py
exercises it on an 8-device mesh against the global-dispatch reference);
wiring it into the scan+remat transformer train step is left as the
documented next step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.nn import core as nn


def local_route(x_loc, expert_ids, gate_vals, n_experts: int, cap: int):
    """Per-shard routing. x_loc: (T_loc, d); expert_ids/gate_vals: (T_loc, k).

    Returns (send (E, cap, d), slot (T_loc*k,) flat slot per assignment
    with E*cap = dropped).
    """
    t, d = x_loc.shape
    k = expert_ids.shape[1]
    flat_e = expert_ids.reshape(-1)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos = jnp.arange(t * k, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = pos < cap
    slot_sorted = jnp.where(keep, sorted_e * cap + pos, n_experts * cap)
    # slot per ORIGINAL assignment index
    slot = jnp.zeros((t * k,), jnp.int32).at[sort_idx].set(slot_sorted)
    token_of_sorted = (sort_idx // k).astype(jnp.int32)
    send = jnp.zeros((n_experts * cap + 1, d), x_loc.dtype)
    send = send.at[slot_sorted].set(x_loc[token_of_sorted], mode="drop")
    return send[:-1].reshape(n_experts, cap, d), slot


def a2a_moe_shard(x_loc, params, n_experts: int, cap: int, *,
                  axis_name: str, n_shards: int, top_k: int,
                  activation: str = "silu"):
    """One shard's MoE forward (call inside shard_map over `axis_name`).

    x_loc: (T_loc, d).  params: same pytree as models/moe.init_moe.
    Returns (T_loc, d).
    """
    t, d = x_loc.shape
    e_loc = n_experts // n_shards
    act = nn.ACTIVATIONS[activation]

    logits = (x_loc @ params["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    send, slot = local_route(x_loc, expert_ids, gate_vals, n_experts, cap)

    # all-to-all: (E, cap, d) -> (E/S, S*cap, d); shard s receives the
    # buffers destined to ITS experts from every shard.
    recv = jax.lax.all_to_all(send.reshape(n_shards, e_loc, cap, d),
                              axis_name, split_axis=0, concat_axis=0)
    h = recv.reshape(e_loc, n_shards * cap, d)

    # local experts' weights (each shard owns E/S experts)
    idx = jax.lax.axis_index(axis_name)
    wg = jax.lax.dynamic_slice_in_dim(params["experts"]["w_gate"],
                                      idx * e_loc, e_loc, 0)
    wi = jax.lax.dynamic_slice_in_dim(params["experts"]["w_in"],
                                      idx * e_loc, e_loc, 0)
    wo = jax.lax.dynamic_slice_in_dim(params["experts"]["w_out"],
                                      idx * e_loc, e_loc, 0)
    y = jnp.einsum("ecf,efd->ecd",
                   act(jnp.einsum("ecd,edf->ecf", h, wg))
                   * jnp.einsum("ecd,edf->ecf", h, wi), wo)

    # reverse all-to-all back to the sending shards
    back = jax.lax.all_to_all(
        y.reshape(e_loc, n_shards, cap, d).swapaxes(0, 1),
        axis_name, split_axis=0, concat_axis=0)        # (1*, E, cap, d)
    y_local = back.reshape(n_experts * cap, d)
    y_flat = jnp.concatenate([y_local, jnp.zeros((1, d), y_local.dtype)], 0)

    per_assign = y_flat[slot]                          # (T_loc*k, d)
    gates = gate_vals.reshape(-1)[:, None].astype(per_assign.dtype)
    out = jnp.sum((per_assign * gates).reshape(t, top_k, d), axis=1)
    return out


def a2a_moe(x, params, moe_cfg, mesh, axis_name: str = "data"):
    """Convenience wrapper: shard_map the dispatch over `axis_name`.

    x: (T, d) global; tokens must divide the axis size.
    Capacity matches models/moe.capacity in expectation (per-shard).
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.moe import capacity
    from repro.parallel.sharding import shard_map_unchecked

    n_shards = mesh.shape[axis_name]
    t = x.shape[0]
    cap = capacity(t // n_shards, moe_cfg)

    fn = partial(a2a_moe_shard, n_experts=moe_cfg.n_experts, cap=cap,
                 axis_name=axis_name, n_shards=n_shards,
                 top_k=moe_cfg.top_k)
    return shard_map_unchecked(
        fn, mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name))(x, params)

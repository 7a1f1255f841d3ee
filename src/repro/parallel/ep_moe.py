"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The jit/GSPMD MoE (models/moe.py) lets the partitioner choose the dispatch
collectives; on the MoE train cells that choice is all-reduce-heavy
(EXPERIMENTS.md §Perf).  This module is the production EP form: devices
along the ``model`` axis own ``E / n_tp`` experts each; every device packs
a fixed-capacity per-destination buffer, one ``lax.all_to_all`` ships
tokens to their expert owners, local experts run, and a second all-to-all
ships results back.  Wire bytes are exactly 2 x cap x d per device pair —
no reductions.

Differentiable (all_to_all transposes to all_to_all), validated against
the GSPMD path in tests/test_ep_moe.py on an 8-device host mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.config.base import ModelConfig


def _local_pack(cfg: ModelConfig, router_logits, xf, n_shards: int,
                cap: int):
    """Per-device: route local tokens, pack per-destination buffers.

    xf: [T_loc, d].  Returns (buffers [n_shards, cap, d],
    meta ids [n_shards, cap, 2] = (local expert idx on dst, src row),
    combine weights [T_loc, k], dst/slot per assignment).
    """
    m = cfg.moe
    E, k = m.num_experts, m.experts_per_token
    e_loc = E // n_shards
    T = xf.shape[0]
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_w, ids = lax.top_k(probs, k)                    # [T, k]
    gate_w = gate_w / jnp.sum(gate_w, axis=-1, keepdims=True)

    flat_ids = ids.reshape(T * k)
    dst = flat_ids // e_loc                              # owner shard
    # slot within the destination buffer: running count per dst
    oh = jax.nn.one_hot(dst, n_shards, dtype=jnp.int32)  # [T*k, S]
    slot = (jnp.cumsum(oh, axis=0) - 1)[jnp.arange(T * k), dst]
    keep = slot < cap
    slot_c = jnp.where(keep, slot, cap)                  # park drops

    buffers = jnp.zeros((n_shards, cap + 1, xf.shape[1]), xf.dtype)
    srcs = jnp.repeat(jnp.arange(T), k)
    buffers = buffers.at[dst, slot_c].set(xf[srcs], mode="drop")
    # metadata rides a separate (small) all_to_all: local expert + src row
    meta = jnp.full((n_shards, cap + 1, 2), -1, jnp.int32)
    meta = meta.at[dst, slot_c, 0].set(flat_ids % e_loc, mode="drop")
    meta = meta.at[dst, slot_c, 1].set(srcs, mode="drop")
    return buffers, meta, gate_w, dst, slot_c, keep


def _expert_ffn(p_loc: Dict[str, Any], xe: jax.Array, eid: jax.Array,
                dt) -> jax.Array:
    """Apply each received token's expert.  xe: [R, d]; eid: [R] local ids."""
    # gather each token's expert weights: fine for e_loc small (EP sliced)
    wg = p_loc["wi_gate"][eid]                          # [R, d, f]
    wu = p_loc["wi_up"][eid]
    wo = p_loc["wo"][eid]
    gate = jnp.einsum("rd,rdf->rf", xe, wg.astype(dt))
    up = jnp.einsum("rd,rdf->rf", xe, wu.astype(dt))
    return jnp.einsum("rf,rfd->rd", jax.nn.silu(gate) * up, wo.astype(dt))


def ep_moe_apply(cfg: ModelConfig, params: Dict[str, Any], x: jax.Array,
                 mesh: Mesh, *, tp_axis: str = "model",
                 batch_axes=("data",), capacity_factor: float = None,
                 ) -> jax.Array:
    """Drop-in EP forward for a [B,S,d] activation on ``mesh``.

    params: {"router" [d,E], "wi_gate"/"wi_up" [E,d,f], "wo" [E,f,d]} —
    expert tensors sharded on their leading dim over ``tp_axis``.
    """
    m = cfg.moe
    dt = jnp.dtype(cfg.dtype)
    n_tp = mesh.shape[tp_axis]
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    B, S, d = x.shape
    T_loc = (B // n_batch) * S
    cf = capacity_factor or m.capacity_factor
    cap = max(int(cf * T_loc * m.experts_per_token / n_tp),
              m.experts_per_token)

    def local(x_loc, router, wg, wu, wo):
        p_loc = {"wi_gate": wg, "wi_up": wu, "wo": wo}
        xf = x_loc.reshape(-1, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        buffers, meta, gate_w, dst, slot_c, keep = _local_pack(
            cfg, logits, xf, n_tp, cap)
        # ship tokens to expert owners (and metadata alongside)
        recv = lax.all_to_all(buffers[:, :cap], tp_axis, 0, 0, tiled=False)
        recv_meta = lax.all_to_all(meta[:, :cap], tp_axis, 0, 0,
                                   tiled=False)
        R = n_tp * cap
        xe = recv.reshape(R, d)
        eid = jnp.maximum(recv_meta.reshape(R, 2)[:, 0], 0)
        valid = recv_meta.reshape(R, 2)[:, 0] >= 0
        ye = _expert_ffn(p_loc, xe, eid, dt)
        ye = jnp.where(valid[:, None], ye, 0.0).astype(dt)
        # ship results back
        back = lax.all_to_all(ye.reshape(n_tp, cap, d), tp_axis, 0, 0,
                              tiled=False)
        # unpack: assignment j of token t sits at (dst[tk], slot[tk])
        Tk = xf.shape[0] * m.experts_per_token
        contrib = back[dst, jnp.minimum(slot_c, cap - 1)]      # [T*k, d]
        contrib = jnp.where(keep[:, None], contrib, 0.0)
        w_flat = gate_w.reshape(Tk)[:, None].astype(dt)
        out = jnp.zeros_like(xf)
        out = out.at[jnp.repeat(jnp.arange(xf.shape[0]),
                                m.experts_per_token)].add(contrib * w_flat)
        return out.reshape(x_loc.shape)

    pspec_x = P(batch_axes, None, None)
    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec_x, P(None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None)),
        out_specs=pspec_x,
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"],
      params["wo"])
    return out

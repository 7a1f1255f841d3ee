"""GQA attention: init, train/prefill forward (chunked, flash-style), decode.

Two implementations of softmax(QK^T)V share one module:
  * ``xla``    – pure jnp, q-block-chunked.  Fully SPMD partitionable;
                 this path is what the multi-pod dry-run lowers
                 (Pallas/Mosaic cannot target the CPU backend).
  * ``pallas`` – the fused flash kernel (kernels/flash_attention.py),
                 forward and backward, called as is: no shard_map, so
                 only where no multi-device mesh is active.
``_attend`` takes the kernel where it applies (``_takes_kernel``) and the
XLA path elsewhere.  Each traced call counts ``attention.flash`` or
``attention.xla`` in ``repro.obs.counts``.

Weights are stored with FLATTENED head dims ([d_model, H*Dh]) so the tensor
dims always divide the 16-way model axis even when num_heads doesn't
(e.g. phi3's 40 heads, GQA kv=8/10) — see DESIGN.md §5.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.config.base import ModelConfig
from repro.models.layers import (
    Params, Axes, dense_init, rmsnorm_init, rmsnorm, apply_rope, apply_mrope,
)
from repro.parallel.context import current_ctx, shard

ATTN_CHUNK = 2048  # q-block size for the chunked XLA path


def attention_init(cfg: ModelConfig, key, *, cross: bool = False) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (cfg.d_model, cfg.q_dim), dt),
        "wk": dense_init(ks[1], (cfg.d_model, cfg.kv_dim), dt),
        "wv": dense_init(ks[2], (cfg.d_model, cfg.kv_dim), dt),
        "wo": dense_init(ks[3], (cfg.q_dim, cfg.d_model), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dt)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dt)
    del cross
    return p


def attention_axes(cfg: ModelConfig) -> Axes:
    a: Axes = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        a["bq"] = ("heads",)
        a["bk"] = ("kv",)
        a["bv"] = ("kv",)
    if cfg.qk_norm:
        a["q_norm"] = (None,)
        a["k_norm"] = (None,)
    return a


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array,
                 positions: Optional[jax.Array],
                 kv_x: Optional[jax.Array] = None,
                 kv_positions: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns q [B,S,Hq,Dh], k/v [B,Skv,Hkv,Dh] with RoPE + qk-norm applied."""
    dt = jnp.dtype(cfg.dtype)
    kv_x = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dh->bsh", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dh->bsh", kv_x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    B, S = x.shape[:2]
    Skv = kv_x.shape[1]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    if positions is not None and cfg.rope_theta > 0.0:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            kp = kv_positions if kv_positions is not None else positions
            k = apply_mrope(k, kp, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            kp = kv_positions if kv_positions is not None else positions
            k = apply_rope(k, kp, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked softmax attention (XLA path)
# ---------------------------------------------------------------------------

def repeat_kv(cfg: ModelConfig, t: jax.Array) -> jax.Array:
    """[B,S,Hkv,Dh] -> [B,S,Hq,Dh].

    GQA's grouped einsum puts the (small) kv-head dim on the model axis,
    which it cannot divide (8 kv heads on a 16-way axis) — GSPMD then
    replicates the scores and inserts a per-chunk all-reduce (measured
    ~1 TB/device/step on qwen2.5-32b train, EXPERIMENTS.md §Perf).
    Expanding K/V to the full q-head count makes every attention einsum
    shard cleanly on heads at the cost of a transient repeat.
    """
    G = cfg.num_heads // cfg.num_kv_heads
    if G == 1:
        return t
    return jnp.repeat(t, G, axis=2)


def _attend_chunked(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                    v: jax.Array, *, causal: bool,
                    q_offset: int = 0) -> jax.Array:
    """softmax(QK^T)V with the q axis processed in blocks via lax.scan.

    Bounds the materialized score tensor to [B,H,chunk,Skv] regardless of
    sequence length (the XLA-level analogue of flash attention's outer loop).
    q: [B,Sq,Hq,Dh]  k,v: [B,Skv,Hkv,Dh]  ->  [B,Sq,Hq,Dh]
    """
    B, Sq, Hq, Dh = q.shape
    Skv = k.shape[1]
    scale = Dh ** -0.5
    k = shard(repeat_kv(cfg, k), "batch", None, "heads_dim", None)
    v = shard(repeat_kv(cfg, v), "batch", None, "heads_dim", None)
    qg = shard(q, "batch", None, "heads_dim", None)

    def block(qb: jax.Array, qpos: jax.Array) -> jax.Array:
        # qb: [B, C, Hq, Dh]; qpos: [C] absolute positions of the q rows
        s = jnp.einsum("bchd,bshd->bchs", qb.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))
        if causal:
            kpos = jnp.arange(Skv)
            mask = qpos[:, None] >= kpos[None, :]         # [C, Skv]
            s = jnp.where(mask[None, :, None, :], s, -1e30)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bchs,bshd->bchd", w, v.astype(jnp.float32))
        return o.astype(q.dtype)

    if Sq <= ATTN_CHUNK:
        out = block(qg, q_offset + jnp.arange(Sq))
    else:
        assert Sq % ATTN_CHUNK == 0, (Sq, ATTN_CHUNK)
        nblk = Sq // ATTN_CHUNK
        qb = qg.reshape(B, nblk, ATTN_CHUNK, Hq, Dh)
        qb = jnp.moveaxis(qb, 1, 0)                       # [nblk, B, C, ...]

        def body(_, xs):
            qblk, i = xs
            pos = q_offset + i * ATTN_CHUNK + jnp.arange(ATTN_CHUNK)
            return None, block(qblk, pos)

        _, ob = jax.lax.scan(body, None, (qb, jnp.arange(nblk)))
        out = jnp.moveaxis(ob, 0, 1).reshape(B, Sq, Hq, Dh)
    return out.reshape(B, Sq, Hq, Dh)


def _takes_kernel(cfg: ModelConfig, q: jax.Array, k: jax.Array) -> bool:
    """Whether ``_attend`` runs the fused kernel: where it compiles and
    nothing must partition it.  The default backend is a TPU, no
    multi-device mesh is active, the head dim is a multiple of 128 and
    both lengths tile into blocks of 128."""
    ctx = current_ctx()
    return (jax.default_backend() == "tpu"
            and (ctx is None or ctx.mesh.size == 1)
            and cfg.head_dim % 128 == 0
            and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0)


def _attend(cfg: ModelConfig, q, k, v, *, causal, q_offset: int = 0):
    if _takes_kernel(cfg, q, k):
        from repro.kernels import ops as kops
        obs.count("attention.flash")
        return kops.flash_attention(q, k, v, causal=causal,
                                    q_offset=q_offset)
    obs.count("attention.xla")
    return _attend_chunked(cfg, q, k, v, causal=causal, q_offset=q_offset)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def attention_apply(cfg: ModelConfig, p: Params, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    kv_x: Optional[jax.Array] = None,
                    kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full (train/prefill) attention.  x: [B,S,d] -> [B,S,d]."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("attention"):
        q, k, v = _project_qkv(cfg, p, x, positions, kv_x, kv_positions)
        o = _attend(cfg, q, k, v, causal=causal)
        B, S = x.shape[:2]
        o = o.reshape(B, S, cfg.q_dim)
        return jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(dt))


def attention_prefill(cfg: ModelConfig, p: Params, x: jax.Array,
                      positions: jax.Array,
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: returns output AND the (flattened-kv) cache entries."""
    dt = jnp.dtype(cfg.dtype)
    q, k, v = _project_qkv(cfg, p, x, positions)
    o = _attend(cfg, q, k, v, causal=True)
    B, S = x.shape[:2]
    out = jnp.einsum("bsh,hd->bsd", o.reshape(B, S, cfg.q_dim),
                     p["wo"].astype(dt))
    cache = {"k": k.reshape(B, S, cfg.kv_dim), "v": v.reshape(B, S, cfg.kv_dim)}
    return out, cache


def attention_decode(cfg: ModelConfig, p: Params, x: jax.Array,
                     positions: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, cache_index: jax.Array,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode step against a [B, Smax, kv_dim] cache.

    x: [B,1,d]; ``cache_index`` is a per-slot [B] vector (continuous
    batching admits requests with different prompt lengths).
    Returns (out [B,1,d], new_cache_k, new_cache_v).
    """
    dt = jnp.dtype(cfg.dtype)
    B = x.shape[0]
    Smax = cache_k.shape[1]
    q, k, v = _project_qkv(cfg, p, x, positions)
    k = k.reshape(B, cfg.kv_dim).astype(cache_k.dtype)
    v = v.reshape(B, cfg.kv_dim).astype(cache_v.dtype)
    bidx = jnp.arange(B)
    cache_k = cache_k.at[bidx, cache_index].set(k, mode="drop")
    cache_v = cache_v.at[bidx, cache_index].set(v, mode="drop")
    kk = repeat_kv(cfg, cache_k.reshape(B, Smax, cfg.num_kv_heads,
                                        cfg.head_dim))
    vv = repeat_kv(cfg, cache_v.reshape(B, Smax, cfg.num_kv_heads,
                                        cfg.head_dim))
    qg = q.reshape(B, 1, cfg.num_heads, cfg.head_dim)
    scale = cfg.head_dim ** -0.5
    s = jnp.einsum("bchd,bshd->bchs", qg.astype(jnp.float32) * scale,
                   kk.astype(jnp.float32))
    # mask positions beyond each slot's index (index = this token's slot)
    valid = (jnp.arange(Smax)[None, :]
             <= cache_index[:, None])[:, None, None, :]
    s = jnp.where(valid, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bchs,bshd->bchd", w, vv.astype(jnp.float32))
    o = o.astype(dt).reshape(B, 1, cfg.q_dim)
    out = jnp.einsum("bsh,hd->bsd", o, p["wo"].astype(dt))
    return out, cache_k, cache_v

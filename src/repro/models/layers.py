"""Shared building blocks: norms, RoPE/M-RoPE, SwiGLU, embeddings.

All parameters are plain pytrees (nested dicts of jnp arrays).  Every module
exposes three functions:

  ``<mod>_init(cfg, key) -> params``     parameter pytree for ONE layer
  ``<mod>_axes(cfg) -> axes``            matching pytree of logical-axis tuples
  ``<mod>_apply(cfg, params, ...)``      forward

Logical axis names (mapped to mesh axes by ``repro.parallel.sharding``):
  "vocab"   – embedding/unembedding vocabulary dim
  "embed"   – d_model dim
  "heads"   – flattened q projection dim (num_heads * head_dim)
  "kv"      – flattened kv projection dim (num_kv_heads * head_dim)
  "mlp"     – feed-forward hidden dim
  "experts" – MoE expert dim
  "inner"   – mamba/rwkv inner dim
  "layers"  – stacked-layer leading axis (never sharded)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig

Params = Dict[str, Any]
Axes = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    """Truncated-normal fan-in init (MaxText-style)."""
    fan_in = shape[in_axis]
    std = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape [head_dim // 2] (float32)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate ``x`` [..., S, H, D] by per-token ``positions`` [..., S]."""
    if theta <= 0.0:  # NoPE (Jamba attention layers)
        return x
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)                    # [half]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: Tuple[int, ...]) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): ``positions`` is [3, ..., S] (t, h, w).

    Frequency index i in [0, head_dim/2) takes its position id from the
    section it falls into: sections = (n_t, n_h, n_w), sum = head_dim/2.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)                    # [half]
    # section id per frequency index
    sec_id = jnp.repeat(jnp.arange(len(sections)),
                        jnp.asarray(sections), total_repeat_length=half)
    # pos_per_freq[..., S, half]: choose t/h/w position per frequency
    pos = jnp.take(positions.astype(jnp.float32), sec_id, axis=0)  # [half, ..., S]
    pos = jnp.moveaxis(pos, 0, -1)                                 # [..., S, half]
    angles = pos * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, (cfg.d_model, d_ff), dt),
        "wi_up": dense_init(k2, (cfg.d_model, d_ff), dt),
        "wo": dense_init(k3, (d_ff, cfg.d_model), dt),
    }


def mlp_axes(cfg: ModelConfig) -> Axes:
    return {
        "wi_gate": ("embed", "mlp"),
        "wi_up": ("embed", "mlp"),
        "wo": ("mlp", "embed"),
    }


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("mlp"):
        gate = jnp.einsum("...d,df->...f", x, p["wi_gate"].astype(dt))
        up = jnp.einsum("...d,df->...f", x, p["wi_up"].astype(dt))
        return jnp.einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                          p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_init(cfg: ModelConfig, key) -> Params:
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p = {"embedding": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dt)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dt)
    if cfg.frontend_embed_dim:
        # modality frontend stub projection (identity-shaped if dims equal)
        p["frontend_proj"] = dense_init(
            ks[2], (cfg.frontend_embed_dim, cfg.d_model), dt)
    return p


def embedding_axes(cfg: ModelConfig) -> Axes:
    a: Axes = {"embedding": ("vocab", "embed")}
    if not cfg.tie_embeddings:
        a["unembed"] = ("embed", "vocab")
    if cfg.frontend_embed_dim:
        a["frontend_proj"] = (None, "embed")
    return a


def embed_tokens(cfg: ModelConfig, p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(cfg.dtype)


def unembed(cfg: ModelConfig, p: Params, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = p["embedding"].T
    else:
        w = p["unembed"]
    return jnp.einsum("...d,dv->...v", h, w.astype(cfg.dtype))

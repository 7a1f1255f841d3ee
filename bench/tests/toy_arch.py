"""A toy architecture module (``model_type`` ``toy``, float32 only), for
the test of the reference's pass over more than one kind of layer.

Two stacks: one leading ``dense`` layer (causal single-head attention and
a SiLU MLP), then ``routed`` layers, each a soft mixture of two experts
whose router has a bias that starts at zero, returning a balance term
that the loss adds.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.reference import attend, f32, rmsnorm, rope

EXPERTS = 2
BALANCE = 0.05

CONFIG = {"name": "toy", "model_type": "toy", "hidden_size": 16,
          "intermediate_size": 24, "vocab_size": 64, "first_dense": 1,
          "num_hidden_layers": 4, "rms_norm_eps": 1e-6, "rope_theta": 1e4,
          "initializer_range": 0.2, "torch_dtype": "float32",
          "tie_word_embeddings": False}


def check(cfg: Dict[str, Any]) -> None:
    pass


def stacks(cfg: Dict[str, Any]):
    k = cfg["first_dense"]
    return [("dense", k), ("routed", cfg["num_hidden_layers"] - k)]


def leaf_specs(cfg: Dict[str, Any]):
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = dict(stacks(cfg))
    Ld, Lr, E = n["dense"], n["routed"], EXPERTS
    return [
        (("embed", "embedding"), (V, D), "normal", None),
        (("embed", "unembed"), (D, V), "normal", None),
        (("final_norm",), (D,), "ones", None),
        (("dense", "norm"), (Ld, D), "ones", "dense"),
        (("dense", "wqkv"), (Ld, D, 3 * D), "normal", "dense"),
        (("dense", "wo"), (Ld, D, D), "normal", "dense"),
        (("dense", "w_in"), (Ld, D, F), "normal", "dense"),
        (("dense", "w_out"), (Ld, F, D), "normal", "dense"),
        (("routed", "norm"), (Lr, D), "ones", "routed"),
        (("routed", "router"), (Lr, D, E), "normal", "routed"),
        (("routed", "bias"), (Lr, E), "zeros", "routed"),
        (("routed", "experts"), (Lr, E, D, D), "normal", "routed"),
    ]


def layer(cfg: Dict[str, Any], stack: str, dot, lp, h, pos):
    B, S, D = h.shape
    x = rmsnorm(h, lp["norm"], cfg["rms_norm_eps"])
    if stack == "dense":
        q, k, v = jnp.split(dot("bsd,de->bse", x, f32(lp["wqkv"])), 3, -1)
        q, k, v = (t.reshape(B, S, 1, D) for t in (q, k, v))
        q = rope(q, pos, cfg["rope_theta"])
        k = rope(k, pos, cfg["rope_theta"])
        h = h + dot("bsd,de->bse", attend(dot, q, k, v), f32(lp["wo"]))
        u = jax.nn.silu(dot("bsd,df->bsf", x, f32(lp["w_in"])))
        return h + dot("bsf,fd->bsd", u, f32(lp["w_out"]))
    p = jax.nn.softmax(dot("bsd,de->bse", x, f32(lp["router"]))
                       + f32(lp["bias"]), -1)
    y = dot("bsd,edf->bsef", x, f32(lp["experts"]))
    h = h + jnp.einsum("bse,bsed->bsd", p, y,
                       precision=jax.lax.Precision.HIGHEST)
    share = jnp.mean(p, (0, 1))
    return h, BALANCE * EXPERTS * jnp.sum(share * share)


def matmul_params(cfg: Dict[str, Any], stack: str) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    if stack == "dense":
        return 4 * D * D + 2 * D * F
    return D * EXPERTS + EXPERTS * D * D


def attn_ops(cfg: Dict[str, Any], stack: str) -> int:
    return 4 * cfg["hidden_size"] if stack == "dense" else 0


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]

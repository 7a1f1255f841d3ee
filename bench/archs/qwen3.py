"""Qwen3 dense decoder (``model_type`` ``qwen3``): what the benchmark knows
of this architecture.

Written from the published architecture (Qwen3ForCausalLM: RMSNorm,
grouped-query attention with per-head RMSNorm on queries and keys, rotary
positions with the rotate-half convention, SwiGLU, tied or untied head).
One stack of identical layers.  The interface every module of
``bench/archs/`` gives is described in :mod:`bench.model`.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from bench.reference import attend, f32, rmsnorm, rope

#: Published keys that must hold these values: the reference implements
#: only this architecture (Qwen3 dense decoder: GQA with per-head QK-norm,
#: rotary positions, SwiGLU, RMSNorm).
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "use_sliding_window": False, "rope_scaling": None}

#: Every width cut, for rehearsing a cell on the CPU.
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2}

#: Widths at which the control (the reference in float8) separates from
#: the program as it does at the cells' own sizes, for its test, by the
#: kind of cell.  At the tiny widths float8 moves a step's loss and its
#: update less than at the cells' sizes, and a random model's best logit
#: stands so far above the rest that float8 still picks it (PERF.md).
CONTROL = {
    "train": dict(TINY, hidden_size=256, head_dim=64, intermediate_size=512,
                  vocab_size=4096),
    "serve": dict(TINY, hidden_size=1024, num_attention_heads=8,
                  num_key_value_heads=2, head_dim=128,
                  intermediate_size=2048, vocab_size=32768),
}


def check(cfg: Dict[str, Any]) -> None:
    for k, v in _FIXED.items():
        if cfg.get(k) != v:
            raise ValueError(f"config {cfg['name']}: {k}={cfg.get(k)!r}, "
                             f"the reference implements only {v!r}")


def program_config(cfg: Dict[str, Any]):
    from repro.config import DENSE, ModelConfig

    return ModelConfig(
        name=cfg["name"], family=DENSE,
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"])


def stacks(cfg: Dict[str, Any]):
    return [("blocks", cfg["num_hidden_layers"])]


def leaf_specs(cfg: Dict[str, Any]):
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, K, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    b = "blocks"
    specs = [
        (("embed", "embedding"), (V, D), "normal", None),
        (("final_norm",), (D,), "ones", None),
        ((b, "ln1"), (L, D), "ones", b),
        ((b, "attn", "wq"), (L, D, H * Dh), "normal", b),
        ((b, "attn", "wk"), (L, D, K * Dh), "normal", b),
        ((b, "attn", "wv"), (L, D, K * Dh), "normal", b),
        ((b, "attn", "wo"), (L, H * Dh, D), "normal", b),
        ((b, "attn", "q_norm"), (L, Dh), "ones", b),
        ((b, "attn", "k_norm"), (L, Dh), "ones", b),
        ((b, "ln2"), (L, D), "ones", b),
        ((b, "mlp", "wi_gate"), (L, D, F), "normal", b),
        ((b, "mlp", "wi_up"), (L, D, F), "normal", b),
        ((b, "mlp", "wo"), (L, F, D), "normal", b),
    ]
    if not cfg["tie_word_embeddings"]:
        specs.insert(1, (("embed", "unembed"), (D, V), "normal", None))
    return specs


def layer(cfg: Dict[str, Any], stack: str, dot, lp, h, pos):
    eps = cfg["rms_norm_eps"]
    B, S, _ = h.shape
    H, K, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    x = rmsnorm(h, lp["ln1"], eps)
    a = lp["attn"]
    q = dot("bsd,dh->bsh", x, f32(a["wq"])).reshape(B, S, H, Dh)
    k = dot("bsd,dh->bsh", x, f32(a["wk"])).reshape(B, S, K, Dh)
    v = dot("bsd,dh->bsh", x, f32(a["wv"])).reshape(B, S, K, Dh)
    q = rope(rmsnorm(q, a["q_norm"], eps), pos, cfg["rope_theta"])
    k = rope(rmsnorm(k, a["k_norm"], eps), pos, cfg["rope_theta"])
    h = h + dot("bsh,hd->bsd", attend(dot, q, k, v), f32(a["wo"]))
    x = rmsnorm(h, lp["ln2"], eps)
    m = lp["mlp"]
    g = dot("bsd,df->bsf", x, f32(m["wi_gate"]))
    u = dot("bsd,df->bsf", x, f32(m["wi_up"]))
    return h + dot("bsf,fd->bsd", jax.nn.silu(g) * u, f32(m["wo"]))


def matmul_params(cfg: Dict[str, Any], stack: str) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def attn_ops(cfg: Dict[str, Any], stack: str) -> int:
    """Score plus value product: 4 * H * Dh."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def head_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]

"""Model operations, counted from shapes (multiply and add count as two).

Only matrix products count: the layers' projections and MLP, the output
head, and attention's score and value products over the causal half.
Recomputation (rematerialisation) is not counted, and neither are norms,
rotary embeddings or softmax.
"""
from __future__ import annotations

from typing import Any, Dict


def _layer_matmul_params(cfg: Dict[str, Any]) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return D * q + 2 * D * kv + q * D + 3 * D * F


def _head_params(cfg: Dict[str, Any]) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def _attn(cfg: Dict[str, Any]) -> int:
    """Score plus value product, per query row and key: 4 * H * Dh."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def forward_tokens(cfg: Dict[str, Any], seq: int) -> float:
    """Forward operations of one causal sequence of ``seq`` tokens, with
    logits at every position."""
    L = cfg["num_hidden_layers"]
    mat = 2 * seq * (L * _layer_matmul_params(cfg) + _head_params(cfg))
    # causal: query i attends to i + 1 keys; sum over i is seq*(seq+1)/2
    return float(mat + L * _attn(cfg) * seq * (seq + 1) / 2)


def train_step(cfg: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) of one step."""
    return 3.0 * batch * forward_tokens(cfg, seq)


def prefill(cfg: Dict[str, Any], prompt: int) -> float:
    """A prompt's forward, with logits at its last position only."""
    L = cfg["num_hidden_layers"]
    return float(2 * prompt * L * _layer_matmul_params(cfg)
                 + 2 * _head_params(cfg)
                 + L * _attn(cfg) * prompt * (prompt + 1) / 2)


def decode_token(cfg: Dict[str, Any], context: int) -> float:
    """One new token attending to ``context`` cached positions and itself."""
    L = cfg["num_hidden_layers"]
    return float(2 * (L * _layer_matmul_params(cfg) + _head_params(cfg))
                 + L * _attn(cfg) * (context + 1))

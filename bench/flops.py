"""Model operations, counted from shapes (multiply and add count as two).

Only matrix products count: the layers' projections and MLP, the output
head, and attention's score and value products over the causal half.
Recomputation (rematerialisation) is not counted, and neither are norms,
rotary embeddings or softmax.  What one layer of each stack holds comes
from the configuration's architecture module (:func:`bench.model.arch`).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

from bench import model


def _counts(cfg: Dict[str, Any]) -> Tuple[int, int, int]:
    """Matrix parameters a token meets in all layers, operations per causal
    query-key pair summed over the layers, and the head's parameters."""
    a = model.arch(cfg)
    stacks = a.stacks(cfg)
    return (sum(n * a.matmul_params(cfg, s) for s, n in stacks),
            sum(n * a.attn_ops(cfg, s) for s, n in stacks),
            a.head_params(cfg))


def forward_tokens(cfg: Dict[str, Any], seq: int) -> float:
    """Forward operations of one causal sequence of ``seq`` tokens, with
    logits at every position."""
    mat, attn, head = _counts(cfg)
    # causal: query i attends to i + 1 keys; sum over i is seq*(seq+1)/2
    return float(2 * seq * (mat + head) + attn * seq * (seq + 1) / 2)


def train_step(cfg: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) of one step."""
    return 3.0 * batch * forward_tokens(cfg, seq)


def prefill(cfg: Dict[str, Any], prompt: int) -> float:
    """A prompt's forward, with logits at its last position only."""
    mat, attn, head = _counts(cfg)
    return float(2 * prompt * mat + 2 * head
                 + attn * prompt * (prompt + 1) / 2)


def decode_token(cfg: Dict[str, Any], context: int) -> float:
    """One new token attending to ``context`` cached positions and itself."""
    mat, attn, head = _counts(cfg)
    return float(2 * (mat + head) + attn * (context + 1))

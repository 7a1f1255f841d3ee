"""Operation counts and peaks, checked against the hand formula."""
from __future__ import annotations

import pytest

from bench import flops, peaks

QWEN3_4B = {"model_type": "qwen3", "hidden_size": 2560,
            "intermediate_size": 9728, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}
QWEN3_8B = {"model_type": "qwen3", "hidden_size": 4096,
            "intermediate_size": 12288, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}


@pytest.mark.parametrize("widths,layer_params", [
    # q 2560x4096, k and v 2560x1024, o 4096x2560, gate/up/down 2560x9728
    (QWEN3_4B, 10_485_760 + 2 * 2_621_440 + 10_485_760 + 3 * 24_903_680),
    # q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up/down 4096x12288
    (QWEN3_8B, 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 50_331_648),
])
def test_train_step_matches_hand_formula(widths, layer_params):
    cfg = dict(widths, num_hidden_layers=9)
    S = 4096
    head = widths["hidden_size"] * 151_936
    # causal attention: 4 * H * Dh per (query, key) pair, S(S+1)/2 pairs
    attn = 9 * 4 * 32 * 128 * S * (S + 1) // 2
    fwd = 2 * S * (9 * layer_params + head) + attn
    assert flops.train_step(cfg, 1, S) == pytest.approx(3 * fwd, rel=1e-12)
    assert flops.train_step(cfg, 4, S) == pytest.approx(12 * fwd, rel=1e-12)


def test_serving_counts_add_up():
    cfg = dict(QWEN3_4B, num_hidden_layers=36)
    # a prompt's forward, then each decode token, versus one forward of
    # the whole sequence less the head on every row but the ones computed
    P, n = 256, 16
    head = 2 * 2560 * 151_936
    served = flops.prefill(cfg, P) + sum(
        flops.decode_token(cfg, P + k) for k in range(n))
    whole = flops.forward_tokens(cfg, P + n) - (P - 1) * head
    assert served == pytest.approx(whole, rel=1e-12)


def test_peaks_known_kind_and_unknown_raises():
    assert peaks.peak("TPU v5 lite", "bf16_flops") == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9000", "bf16_flops")

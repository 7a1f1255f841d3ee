"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels import ops

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 2, 2, 128, 128, 64),       # MHA square
    (2, 4, 2, 256, 256, 64),       # GQA
    (1, 8, 1, 128, 128, 128),      # MQA, MXU-width head
    (2, 2, 2, 128, 384, 64),       # cross/kv-longer (q_offset causal)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, dtype, causal):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    q_offset = Skv - Sq if causal else 0
    out = flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("B,H,S,D,chunk", [
    (1, 2, 64, 32, 16),
    (2, 3, 128, 64, 64),
    (1, 1, 256, 64, 32),
    (1, 2, 256, 64, 64),           # rwkv6-3b head width, default chunk
])
def test_rwkv6_scan_sweep(B, H, S, D, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, H, S, D))))
    u = jax.random.normal(ks[4], (H, D))
    out = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    expect = ref.rwkv6_ref(r, k, v, w, u)
    # f32 accumulation-order differences grow with S*D; scale-aware tol
    scale = float(np.max(np.abs(np.asarray(expect)))) + 1.0
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(expect) / scale,
                               rtol=2e-4, atol=2e-4)


def test_rwkv6_strong_decay_numerics():
    """Very small decays must not overflow the chunked log-space form."""
    B, H, S, D = 1, 1, 128, 32
    ks = jax.random.split(KEY, 4)
    r = jax.random.normal(ks[0], (B, H, S, D))
    k = jax.random.normal(ks[1], (B, H, S, D))
    v = jax.random.normal(ks[2], (B, H, S, D))
    w = jnp.full((B, H, S, D), 1e-6)        # near-total forgetting
    u = jax.random.normal(ks[3], (H, D))
    out = rwkv6_scan(r, k, v, w, u, chunk=32, interpret=True)
    expect = ref.rwkv6_ref(r, k, v, w, u)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("B,S,di,N,chunk,block_d", [
    (1, 64, 32, 8, 16, 32),
    (2, 128, 64, 16, 64, 32),
    (1, 256, 128, 16, 32, 64),
    (1, 128, 512, 16, 64, 256),    # jamba d_state, default block_d/chunk
])
def test_mamba_scan_sweep(B, S, di, N, chunk, block_d):
    ks = jax.random.split(KEY, 5)
    A = -jnp.exp(jax.random.normal(ks[0], (di, N)))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    b = jax.random.normal(ks[2], (B, S, N))
    c = jax.random.normal(ks[3], (B, S, N))
    x = jax.random.normal(ks[4], (B, S, di))
    out = mamba_scan(A, dt, b, c, x, chunk=chunk, block_d=block_d,
                     interpret=True)
    expect = ref.mamba_ref(A, dt, b, c, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sizes", [
    [128, 128, 128, 128],
    [100, 0, 300, 112],
    [0, 0, 512, 0],
    [1, 2, 3, 506],
])
def test_gmm_sweep(sizes):
    M, K, N, G = sum(sizes), 64, 128, len(sizes)
    ks = jax.random.split(KEY, 2)
    lhs = jax.random.normal(ks[0], (M, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (G, K, N), jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        out = ops.gmm_sorted(lhs, rhs, np.asarray(sizes), block_m=128)
    expect = ref.gmm_ref(lhs, rhs, jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_flash_matches_model_xla_path():
    """The model's chunked-XLA attention and the Pallas kernel agree."""
    from repro.configs import get_tiny_config
    from repro.models import init_params, forward
    from repro.data.batches import make_batch
    cfg = get_tiny_config("qwen3-8b").replace(head_dim=32)
    p = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 128)
    lo_x, _ = forward(cfg.replace(attention_impl="xla"), p, batch)
    with pltpu.force_tpu_interpret_mode():
        lo_k, _ = forward(cfg.replace(attention_impl="pallas"), p, batch)
    np.testing.assert_allclose(np.asarray(lo_x, np.float32),
                               np.asarray(lo_k, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_kernel_off_tpu_raises_unless_interpret_asked():
    """No silent fallback: off the TPU a kernel runs only when asked to."""
    q =jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        ops.flash_attention(q, q, q)

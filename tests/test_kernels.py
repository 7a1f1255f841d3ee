"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import functools

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
from repro.models import attention

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _qkv(B, Hq, Hkv, Sq, Skv, D, dtype, key=KEY):
    """q [B,Sq,Hq,D], k/v [B,Skv,Hkv,D] and a cotangent like q."""
    ks = jax.random.split(key, 4)
    mk = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(dtype)
    return (mk(ks[0], (B, Sq, Hq, D)), mk(ks[1], (B, Skv, Hkv, D)),
            mk(ks[2], (B, Skv, Hkv, D)), mk(ks[3], (B, Sq, Hq, D)))


def _ref_model_layout(q, k, v, **kw):
    """``ref.attention_ref`` in the model layout [B,S,H,D], f32 math."""
    t = lambda x: jnp.swapaxes(x, 1, 2).astype(jnp.float32)
    return jnp.swapaxes(ref.attention_ref(t(q), t(k), t(v), **kw), 1, 2)


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 2, 2, 128, 128, 64),       # MHA square
    (2, 4, 2, 256, 256, 64),       # GQA
    (1, 8, 1, 128, 128, 128),      # MQA, MXU-width head
    (2, 2, 2, 128, 384, 64),       # cross/kv-longer (q_offset causal)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, dtype, causal):
    q, k, v, _ = _qkv(B, Hq, Hkv, Sq, Skv, D, dtype)
    q_offset = Skv - Sq if causal else 0
    out = flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                          block_q=64, block_k=64, interpret=True)
    expect = _ref_model_layout(q, k, v, causal=causal, q_offset=q_offset)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("Sq,Skv,bq,bk,raises", [
    (64, 64, None, None, False),     # under 128 rows: one block of 64
    (320, 320, None, None, True),    # no block of 512/256/128 divides it
    (128, 4000, None, None, True),   # nor this kv length
    (384, 384, 192, 192, True),      # a block over 128 rows not a multiple
])
def test_flash_attention_blocks_from_lengths(Sq, Skv, bq, bk, raises):
    """Blocks come from the lengths; a length over 128 rows that no block
    divides is refused with a clear error, not at trace or in VMEM."""
    q, k, v, _ = _qkv(1, 2, 1, Sq, Skv, 64, jnp.float32)
    call = functools.partial(flash_attention, q, k, v, causal=False,
                             block_q=bq, block_k=bk, interpret=True)
    if raises:
        with pytest.raises(ValueError, match="multiple of 128"):
            call()
        return
    np.testing.assert_allclose(np.asarray(call()), np.asarray(
        _ref_model_layout(q, k, v, causal=False)), **_tol(jnp.float32))


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D,bq,bk", [
    (1, 2, 2, 128, 128, 64, 32, 32),     # MHA, 4x4 blocks: half skipped
    (2, 8, 2, 128, 128, 32, 64, 32),     # GQA G=4, bq != bk
    (1, 4, 1, 64, 256, 32, 32, 64),      # Sq != Skv, causal via q_offset
    (1, 4, 2, 128, 128, 128, 128, 128),  # one block, MXU-width head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_grad(B, Hq, Hkv, Sq, Skv, D, bq, bk, dtype,
                              causal):
    """The kernel's custom VJP (flash_dq, flash_dkv) against autodiff of
    the plain f32 reference."""
    q, k, v, do = _qkv(B, Hq, Hkv, Sq, Skv, D, dtype)
    kw = dict(causal=causal, q_offset=Skv - Sq if causal else 0)
    out, vjp = jax.vjp(lambda *a: flash_attention(
        *a, block_q=bq, block_k=bk, interpret=True, **kw), q, k, v)
    f32 = lambda x: x.astype(jnp.float32)
    expect, vjp_ref = jax.vjp(lambda *a: _ref_model_layout(*a, **kw),
                              f32(q), f32(k), f32(v))
    for name, got, want in zip(("o", "dq", "dk", "dv"),
                               (out,) + vjp(do), (expect,) + vjp_ref(f32(do))):
        assert got.dtype == dtype, name
        want = np.asarray(want)
        scale = float(np.max(np.abs(want)))
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got, np.float32) / scale,
                                   want / scale, rtol=tol, atol=tol,
                                   err_msg=name)


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


def test_flash_matches_model_xla_path(monkeypatch):
    """The model's chunked-XLA attention and the Pallas kernel agree."""
    from repro.configs import get_tiny_config
    from repro.models import init_params, forward
    from repro.data.batches import make_batch
    cfg = get_tiny_config("qwen3-8b").replace(head_dim=32)
    p = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 128)
    lo_x, _ = forward(cfg, p, batch)
    monkeypatch.setattr(attention, "_takes_kernel", lambda *a: True)
    with pltpu.force_tpu_interpret_mode():
        lo_k, _ = forward(cfg, p, batch)
    np.testing.assert_allclose(np.asarray(lo_x, np.float32),
                               np.asarray(lo_k, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 5e-2)])
def test_train_step_gradients_kernel_vs_xla_path(monkeypatch, dtype, tol):
    """The gradient a train step takes (``loss_fn`` under full remat, the
    layers scanned) is the same through the kernel as through XLA: to f32
    rounding in f32, and in bf16 to the kernel's bf16 P and dS (the CPU's
    XLA keeps them f32).  The kernel runs in the plain interpreter: the
    TPU interpreter's callbacks cannot be rematerialised."""
    from repro.configs import get_tiny_config
    from repro.models import init_params, loss_fn
    from repro.data.batches import make_batch
    cfg = get_tiny_config("qwen3-8b").replace(head_dim=32, remat="full",
                                              dtype=dtype)
    p = init_params(cfg, KEY)
    batch = make_batch(cfg, 2, 128)

    def grads():
        return jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(p)

    g_x = grads()
    monkeypatch.setattr(attention, "_takes_kernel", lambda *a: True)
    monkeypatch.setattr(ops, "flash_attention", functools.partial(
        flash_attention, interpret=True))
    g_k = grads()
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_x),
                            jax.tree.leaves(g_k)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        gap = np.linalg.norm(a - b) / (np.linalg.norm(a) + 1e-12)
        assert gap < tol, (jax.tree_util.keystr(path), gap)


def _attend_counted(cfg, q, k):
    """``_attend``'s output and which path it counted."""
    from repro import obs
    from repro.models.attention import _attend
    before = dict(obs.counts)
    out = _attend(cfg, q, k, k, causal=True)
    moved = {n for n in ("attention.flash", "attention.xla")
             if obs.counts.get(n, 0) != before.get(n, 0)}
    assert len(moved) == 1, moved
    return out, moved.pop()


@pytest.mark.parametrize("S,D,path", [
    (128, 128, "attention.flash"),
    (256, 128, "attention.flash"),
    (128, 64, "attention.xla"),        # head dim not lane-aligned
    (192, 128, "attention.xla"),       # length does not tile by 128
])
def test_attention_takes_kernel_where_it_applies(monkeypatch, S, D, path):
    """XLA on the CPU; on a TPU (here the
    interpreter, the backend reported as a TPU) the kernel where the head
    dim and lengths tile, XLA elsewhere, with the same numbers."""
    from repro.configs import get_tiny_config
    cfg = get_tiny_config("qwen3-8b").replace(head_dim=D)
    q, k, _, _ = _qkv(1, cfg.num_heads, cfg.num_kv_heads, S, S, D,
                      jnp.bfloat16)
    want, counted = _attend_counted(cfg, q, k)
    assert counted == "attention.xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        got, counted = _attend_counted(cfg, q, k)
    assert counted == path
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_attention_takes_xla_under_a_multi_device_mesh(monkeypatch):
    """The kernel is called as is, without shard_map: a mesh of more than
    one device keeps the partitionable XLA path; a one-device mesh not."""
    from types import SimpleNamespace
    from repro.configs import get_tiny_config
    from repro.models.attention import _takes_kernel
    from repro.parallel.context import sharding_ctx
    cfg = get_tiny_config("qwen3-8b").replace(head_dim=128)
    q = jax.ShapeDtypeStruct((1, 256, cfg.num_heads, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 256, cfg.num_kv_heads, 128), jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _takes_kernel(cfg, q, k)
    for size, takes in ((4, False), (1, True)):
        with sharding_ctx(SimpleNamespace(mesh=SimpleNamespace(size=size))):
            assert _takes_kernel(cfg, q, k) is takes


def test_kernel_off_tpu_raises_unless_interpret_asked():
    """No silent fallback: off the TPU a kernel runs only when asked to."""
    q =jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        ops.flash_attention(q, q, q)

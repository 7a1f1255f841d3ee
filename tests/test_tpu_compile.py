"""The Pallas kernels of the main path compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) v5e chip, which refuses what interpret mode lets
through — unsupported primitives, unaligned tiles, too much VMEM.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and it keeps it until it exits.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.rwkv6_scan import rwkv6_scan


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        from jax.experimental import topologies
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compiles_to_mosaic(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_qwen3_4b_widths(spec):
    q = spec((1, 4096, 32, 128), jnp.bfloat16)
    kv = spec((1, 4096, 8, 128), jnp.bfloat16)
    _compiles_to_mosaic(lambda q, k, v: flash_attention(q, k, v, causal=True),
                        q, kv, kv)


def test_flash_attention_backward_compiles_at_qwen3_4b_widths(spec):
    """Forward and backward (flash_fwd, flash_dq, flash_dkv) through the
    model's entry point, with the blocks it picks."""
    q = spec((1, 4096, 32, 128), jnp.bfloat16)
    kv = spec((1, 4096, 8, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v).astype(jnp.float32))

    fn = jax.value_and_grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, kv, kv).compile().as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert name in text, name


def test_rwkv6_scan_compiles_at_rwkv6_3b_widths(spec):
    x = spec((1, 40, 4096, 64), jnp.float32)
    _compiles_to_mosaic(rwkv6_scan, x, x, x, x, spec((40, 64), jnp.float32))


def test_mamba_scan_compiles_at_jamba_widths(spec):
    di, n, s = 16384, 16, 4096
    _compiles_to_mosaic(
        mamba_scan, spec((di, n), jnp.float32), spec((1, s, di), jnp.float32),
        spec((1, s, n), jnp.float32), spec((1, s, n), jnp.float32),
        spec((1, s, di), jnp.float32))

"""Pallas TPU grouped matmul (megablox-lite) for MoE expert FFNs.

lhs [M, K] holds tokens sorted by expert; rhs [G, K, N] stacks expert
weights.  The ops.py wrapper pads each group's row count to a multiple of
``block_m``, so every m-tile maps to exactly ONE group — the group id per
tile is passed as a scalar-prefetch operand and selects the rhs block via
its index_map.  Accumulation over K tiles happens in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_K = 512
DEFAULT_BLOCK_N = 512


def _gmm_kernel(gid_ref, lhs_ref, rhs_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        lhs_ref[...].astype(jnp.float32), rhs_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def gmm(lhs: jax.Array, rhs: jax.Array, tile_group_ids: jax.Array, *,
        block_m: int = DEFAULT_BLOCK_M, block_k: int = DEFAULT_BLOCK_K,
        block_n: int = DEFAULT_BLOCK_N, interpret: bool = False) -> jax.Array:
    """lhs: [M,K]; rhs: [G,K,N]; tile_group_ids: [M/block_m] -> [M,N].

    Requires group boundaries aligned to block_m (ops.py pads to this).
    """
    M, K = lhs.shape
    G, _, N = rhs.shape
    block_m = min(block_m, M)
    block_k = min(block_k, K)
    block_n = min(block_n, N)
    assert M % block_m == 0 and K % block_k == 0 and N % block_n == 0
    nm, nk, nn = M // block_m, K // block_k, N // block_n
    assert tile_group_ids.shape == (nm,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki, gid: (mi, ki)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda mi, ni, ki, gid: (gid[mi], ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki, gid: (mi, ni)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    kernel = functools.partial(_gmm_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_group_ids, lhs, rhs)

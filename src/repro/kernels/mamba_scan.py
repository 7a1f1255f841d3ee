"""Pallas TPU chunked selective-scan (Mamba-1 SSM).

Grid = (B, di/block_d, S/chunk); the SSM state h^T [N, block_d] lives in VMEM
scratch across the sequential chunk axis, so the recurrence never round-trips
HBM.  Within a chunk the recurrence is stepped with a fori_loop over VMEM
tiles (the update is elementwise VPU work — there is no MXU contraction to
tile, N=16 — so the win is state residency + input tile reuse).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64
DEFAULT_BLOCK_D = 256


def _mamba_kernel(A_ref, dt_ref, b_ref, c_ref, x_ref, o_ref, h_scr, *,
                  chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    # The state is kept transposed, h^T [N, bd], so that each step's dt/x
    # rows [1, bd] broadcast over it without a relayout.  b, c and A turn
    # to [N, *] once per chunk through an exact identity matmul (NT form).
    N = A_ref.shape[1]
    eye = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1),
                    1.0, 0.0)

    def to_cols(m):                            # [R, N] -> [N, R]
        return jax.lax.dot_general(eye, m.astype(jnp.float32),
                                   (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    At = to_cols(A_ref[...])                   # [N, bd]
    bt = to_cols(b_ref[0])                     # [N, C]
    ct = to_cols(c_ref[0])                     # [N, C]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, h):
        dt_t = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # [1, bd]
        x_t = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)     # [1, bd]
        sel = lane == t
        b_t = jnp.sum(jnp.where(sel, bt, 0.0), axis=1, keepdims=True)
        c_t = jnp.sum(jnp.where(sel, ct, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt_t * At) * h + b_t * (dt_t * x_t)          # [N, bd]
        y = jnp.sum(h * c_t, axis=0, keepdims=True)              # [1, bd]
        o_ref[0, pl.ds(t, 1), :] = y.astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])


def mamba_scan(A: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
               x: jax.Array, *, chunk: int = DEFAULT_CHUNK,
               block_d: int = DEFAULT_BLOCK_D,
               interpret: bool = False) -> jax.Array:
    """A: [di,N]; dt,x: [B,S,di]; b,c: [B,S,N] -> y [B,S,di] float32."""
    B, S, di = x.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0 and di % block_d == 0
    nc, nd = S // chunk, di // block_d

    kernel = functools.partial(_mamba_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((block_d, N), lambda bi, di_, ci: (di_, 0)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda bi, di_, ci: (bi, ci, di_)),
            pl.BlockSpec((1, chunk, N), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, di_, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, block_d),
                         lambda bi, di_, ci: (bi, ci, di_)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda bi, di_, ci: (bi, ci, di_)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A, dt, b, c, x)
    return out

"""Pallas TPU chunked WKV6 scan (RWKV6 "Finch" time mixing).

Algorithm (flash-linear-attention style, log-space chunking): for a chunk
of C tokens with per-token per-channel decay w_t ∈ (0,1),

    L_t  = Σ_{j<=t} log w_j                     (chunk-local, L_0 = 0)
    y_t  = (r_t ⊙ e^{L_{t-1}}) S_0              (inter-chunk, matmul)
         + Σ_{s<t} [r_t·k_s ⊙ e^{L_{t-1}-L_s}] v_s   (intra, [C,C,D] masked)
         + (r_t · u ⊙ k_t) v_t                  (diagonal bonus term)
    S'   = diag(e^{L_C}) S_0 + Σ_s (k_s ⊙ e^{L_C - L_s})^T v_s

All exponentials have non-positive arguments, so the chunked form is
numerically safe.  The recurrent state S [D,D] stays in VMEM scratch across
the (sequential) chunk grid axis — the whole sequence makes zero HBM
round-trips for state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)          # [C, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)          # [1, D]
    S0 = s_scr[...]                           # [D, D]

    logw = jnp.log(jnp.maximum(w, 1e-37))
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # prefix sum as a lower-triangular-ones matmul (Mosaic has no cumsum)
    L = jax.lax.dot_general(jnp.where(row >= col, 1.0, 0.0), logw,
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)  # [C, D]
    L_prev = L - logw                         # [C, D]  (= L_{t-1})

    # inter-chunk: (r ⊙ e^{L_prev}) @ S0
    r_dec = r * jnp.exp(L_prev)
    y = jax.lax.dot_general(r_dec, S0, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # intra-chunk: A[t,s] = Σ_d r[t,d] k[s,d] e^{L_prev[t,d]-L[s,d]} (s<t)
    expo = L_prev[:, None, :] - L[None, :, :]            # [C, C, D]
    t3 = jax.lax.broadcasted_iota(jnp.int32, expo.shape, 0)
    s3 = jax.lax.broadcasted_iota(jnp.int32, expo.shape, 1)
    gated = jnp.where(t3 > s3, jnp.exp(expo), 0.0)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * gated, axis=-1)
    # diagonal bonus: r_t · (u ⊙ k_t)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)     # [C, 1]
    A = A + jnp.where(row == col, diag, 0.0)
    y = y + jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update: S' = diag(e^{L_C}) S0 + (k ⊙ e^{L_C - L_s})^T v
    L_total = L[-1:, :]                                   # [1, D]
    k_dec = k * jnp.exp(L_total - L)
    s_scr[...] = (jnp.exp(L_total).T * S0
                  + jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    o_ref[0] = y.astype(o_ref.dtype)


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
               interpret: bool = False) -> jax.Array:
    """r,k,v,w: [B,H,S,D]; u: [H,D] -> y [B,H,S,D] float32."""
    B, H, S, D = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    resh = lambda t: t.reshape(B * H, S, D)
    ur = jnp.broadcast_to(u[None], (B, H, D)).reshape(B * H, 1, D)

    def x_map(bh, ci):
        return (bh, ci, 0)

    def u_map(bh, ci):
        return (bh, 0, 0)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[pl.BlockSpec((1, chunk, D), x_map)] * 4
        + [pl.BlockSpec((1, 1, D), u_map)],
        out_specs=pl.BlockSpec((1, chunk, D), x_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(resh(r), resh(k), resh(v), resh(w), ur)
    return out.reshape(B, H, S, D)

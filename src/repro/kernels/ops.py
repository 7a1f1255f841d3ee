"""Jit'd public wrappers for the Pallas kernels (model layout in and out).

The kernels compile to Mosaic for the TPU.  On any other backend a call
raises unless its caller asked for interpret mode, e.g. under
``pltpu.force_tpu_interpret_mode()``; nothing here chooses it for them.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _rw
from repro.kernels import mamba_scan as _mb
from repro.kernels import gmm as _gmm


@functools.partial(jax.jit, static_argnames=("causal", "q_offset",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D] -> [B,Sq,Hq,D], differentiable.

    Blocks default to the largest of 512/256/128 that divides each
    length (``flash_attention.pick_block``)."""
    return _fa.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                               block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, *, chunk: int = _rw.DEFAULT_CHUNK):
    """r,k,v,w: [B,S,H,D]; u: [H,D] -> [B,S,H,D] (model layout)."""
    tr = lambda t: jnp.swapaxes(t, 1, 2)
    o = _rw.rwkv6_scan(tr(r), tr(k), tr(v), tr(w), u, chunk=chunk)
    return jnp.swapaxes(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk", "block_d"))
def mamba_scan(A, dt, b, c, x, *, chunk: int = _mb.DEFAULT_CHUNK,
               block_d: int = _mb.DEFAULT_BLOCK_D):
    """A: [di,N]; dt,x: [B,S,di]; b,c: [B,S,N] -> y [B,S,di]."""
    return _mb.mamba_scan(A, dt, b, c, x, chunk=chunk, block_d=block_d)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n"))
def gmm_padded(lhs, rhs, tile_group_ids, *,
               block_m: int = _gmm.DEFAULT_BLOCK_M,
               block_k: int = _gmm.DEFAULT_BLOCK_K,
               block_n: int = _gmm.DEFAULT_BLOCK_N):
    return _gmm.gmm(lhs, rhs, tile_group_ids, block_m=block_m,
                    block_k=block_k, block_n=block_n)


def gmm_sorted(lhs, rhs, group_sizes, *, block_m: int = _gmm.DEFAULT_BLOCK_M):
    """Convenience: pad each group's rows to block_m and run the kernel.

    lhs rows must already be sorted by group.  Returns [M, N] unpadded.
    Group sizes must be concrete (host-side routing metadata).
    """
    import numpy as np
    sizes = np.asarray(group_sizes)
    G = rhs.shape[0]
    starts = np.concatenate([[0], np.cumsum(sizes)])
    padded = [int(-(-s // block_m) * block_m) if s else 0 for s in sizes]
    total = sum(padded) or block_m
    out_rows = []
    tile_ids = []
    lhs_p = jnp.zeros((total, lhs.shape[1]), lhs.dtype)
    off = 0
    for g in range(G):
        if sizes[g] == 0:
            continue
        seg = lhs[starts[g]:starts[g + 1]]
        lhs_p = jax.lax.dynamic_update_slice(lhs_p, seg, (off, 0))
        tile_ids += [g] * (padded[g] // block_m)
        out_rows.append((off, int(sizes[g]), starts[g]))
        off += padded[g]
    if not tile_ids:
        tile_ids = [0]
    y_p = gmm_padded(lhs_p, rhs, jnp.asarray(tile_ids, jnp.int32),
                     block_m=block_m)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), lhs.dtype)
    for off, n, start in out_rows:
        out = jax.lax.dynamic_update_slice(
            out, jax.lax.dynamic_slice(y_p, (off, 0), (n, rhs.shape[2])),
            (start, 0))
    return out

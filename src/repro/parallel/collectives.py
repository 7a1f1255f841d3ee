"""Explicit collectives for shard_map regions.

``hierarchical_psum``: reduce-scatter on the fast intra-pod axis, psum on
the slow cross-pod axis over the scattered shard, then all-gather — the
cross-pod link carries 1/|data| of the bytes a flat psum would ship, which
is the collective-layer reading of XUFS's cache-local/WAN-async split.

``compressed_psum``: int8-quantized cross-axis psum (pairs with the error
feedback in optim/compress.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def hierarchical_psum(x: jax.Array, pod_axis: str, inner_axis: str,
                      ) -> jax.Array:
    """psum over (pod_axis, inner_axis) with pod traffic minimized.

    Requires x's leading dim divisible by the inner axis size.
    """
    n_inner = lax.axis_size(inner_axis)
    lead = x.shape[0]
    if lead % n_inner != 0:
        # fall back: flat psum (correct, just not bandwidth-optimal)
        return lax.psum(x, (pod_axis, inner_axis))
    # reduce-scatter within pod: each inner rank owns a 1/n_inner slice
    shard = lax.psum_scatter(x, inner_axis, scatter_dimension=0,
                             tiled=True)
    # cross-pod reduce touches only the owned slice
    shard = lax.psum(shard, pod_axis)
    # all-gather the slices back within the pod
    return lax.all_gather(shard, inner_axis, axis=0, tiled=True)


def compressed_psum(x: jax.Array, axis: str, *, dequant_dtype=jnp.float32,
                    ) -> jax.Array:
    """Quantize-locally-then-reduce psum across ``axis``.

    Each rank quantizes its contribution to int8 (per-tensor scale) before
    the reduction; the reduction itself sums the *dequantized* values so
    the result is exact given the quantized contributions.  On-wire int8
    (uniform-scale) is a transport detail the simulation abstracts; the
    quantization error this op introduces is what optim/compress.py's
    error feedback re-injects.
    """
    scale = jnp.max(jnp.abs(x)) / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    contrib = q.astype(dequant_dtype) * scale
    return lax.psum(contrib, axis)

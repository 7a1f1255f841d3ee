"""Pallas TPU flash attention (GQA, causal), forward and backward.

Layout: the model's own, q [B, Sq, Hq, D] and k/v [B, Skv, Hkv, D], read
as [B, S, H*D].  A block is one head's [block, D] slice, so nothing is
transposed, and a q head reads its kv head (h // G, G = Hq / Hkv) through
the ``index_map``: k and v are never repeated per q head.

* ``flash_fwd``: grid (B, Hq, Sq/bq, Skv/bk), kv innermost.  Online
  softmax: the [bq, D] accumulator, row max and row sum stay in VMEM
  across the kv blocks; the scores never leave VMEM.  Returns ``o`` and
  each row's log-sum-exp (f32), the backward's residuals.
* Backward, as FlashAttention-2: ``delta = rowsum(dO * O)`` in XLA, then
  ``flash_dq`` (grid as the forward's, dq accumulated over kv blocks) and
  ``flash_dkv`` (grid (B, Hkv, Skv/bk, G * Sq/bq): dk and dv accumulated
  over the q blocks of all G q heads that share the kv head).  Both
  recompute ``P = exp(S - lse)`` in VMEM.
* Causal: a block wholly above the diagonal computes nothing (``pl.when``)
  and its ``index_map`` repeats the last block needed (the first, in
  ``flash_dkv``), so the pipeline fetches nothing for it; only blocks the
  diagonal crosses are masked.

Precision: the MXU takes the inputs' dtype (bf16 in the model) with f32
accumulation; P, dO and dS enter it in that dtype too.  The softmax
statistics, ``delta`` and the accumulators are f32; the forward keeps the
row max and sum replicated over a vreg's 128 lanes.  q is scaled once,
in XLA ahead of the kernels, in f32 before the cast back, as XLA's
default precision does for an f32 einsum of ``q * scale``; ``flash_dq``
applies the scale to dq in f32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
BLOCKS = (512, 256, 128)


def pick_block(n: int) -> int:
    """The largest of :data:`BLOCKS` that divides ``n`` (else ``n``)."""
    return next((b for b in BLOCKS if n % b == 0), n)


@dataclasses.dataclass(frozen=True)
class _Geo:
    """Static shape of one call: blocks, causality and counts."""
    causal: bool
    q_offset: int
    bq: int
    bk: int
    nq: int
    nk: int
    hq: int
    hkv: int
    d: int
    interpret: bool

    @property
    def g(self) -> int:
        return self.hq // self.hkv

    @property
    def scale(self) -> float:
        return self.d ** -0.5

    def last_kv(self, qi):
        """Last kv block that q block ``qi`` sees."""
        return (self.q_offset + (qi + 1) * self.bq - 1) // self.bk

    def first_q(self, ki):
        """First q block that sees kv block ``ki``."""
        return jnp.maximum(0, (ki * self.bk - self.q_offset) // self.bq)


def _visit(geo: _Geo, qi, ki, body) -> None:
    """Run ``body(masked)`` on block (qi, ki): unmasked below the diagonal,
    masked where it crosses, not at all above it."""
    if not geo.causal:
        body(False)
        return
    q_lo = geo.q_offset + qi * geo.bq
    q_hi = q_lo + geo.bq - 1
    k_lo = ki * geo.bk
    k_hi = k_lo + geo.bk - 1
    pl.when(k_hi <= q_lo)(lambda: body(False))
    pl.when((k_lo <= q_hi) & (k_hi > q_lo))(lambda: body(True))


def _visible(geo: _Geo, qi, ki, shape, q_axis: int):
    """[.., ..] bool: query position >= key position, q rows on ``q_axis``."""
    qpos = geo.q_offset + qi * geo.bq + jax.lax.broadcasted_iota(
        jnp.int32, shape, q_axis)
    kpos = ki * geo.bk + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                  1 - q_axis)
    return qpos >= kpos


def _nt(a, b):
    """a @ b.T with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """A lane-replicated [rows, LANES] statistic as [rows, n]."""
    return jnp.tile(x, (1, n // LANES)) if n % LANES == 0 else x[:, :n]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, geo: _Geo):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _nt(q_ref[...], k_ref[...])                     # [bq, bk]
        if masked:
            s = jnp.where(_visible(geo, qi, ki, s.shape, 0), s, NEG_INF)
        m_prev = m_scr[...]                                 # [bq, LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, geo.bk))
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = _lanes(alpha, geo.d) * acc_scr[...] + _nn(
            p.astype(v_ref.dtype), v_ref[...])
        m_scr[...] = m_new

    _visit(geo, qi, ki, body)

    @pl.when(ki == geo.nk - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / _lanes(l, geo.d)).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l)).T[:1]   # a column as a row


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, geo: _Geo):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        s = _nt(q_ref[...], k_ref[...])                     # [bq, bk]
        if masked:
            s = jnp.where(_visible(geo, qi, ki, s.shape, 0), s, NEG_INF)
        p = jnp.exp(s - jnp.expand_dims(lse_ref[0], 1))
        dp = _nt(do_ref[...], v_ref[...])
        ds = p * (dp - jnp.expand_dims(delta_ref[0], 1))
        acc_scr[...] += _nn(ds.astype(k_ref.dtype), k_ref[...])

    _visit(geo, qi, ki, body)

    @pl.when(ki == geo.nk - 1)
    def _finish():
        dq_ref[...] = (acc_scr[...] * geo.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, geo: _Geo):
    ki, j = pl.program_id(2), pl.program_id(3)
    qi = j % geo.nq

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        q = q_ref[...]
        s = _nt(k_ref[...], q)                              # [bk, bq]
        if masked:
            s = jnp.where(_visible(geo, qi, ki, s.shape, 1), s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])
        do = do_ref[...]
        dv_scr[...] += _nn(p.astype(do.dtype), do)
        dp = _nt(v_ref[...], do)
        ds = p * (dp - delta_ref[...])
        dk_scr[...] += _nn(ds.astype(q.dtype), q)

    _visit(geo, qi, ki, body)

    @pl.when(j == geo.g * geo.nq - 1)
    def _finish():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# calls
# ---------------------------------------------------------------------------

#: The last grid axis carries the accumulators; the others are independent.
_PARAMS = pltpu.CompilerParams(dimension_semantics=(
    "parallel", "parallel", "parallel", "arbitrary"))


def _kv_block(geo: _Geo, qi, ki):
    return jnp.minimum(ki, geo.last_kv(qi)) if geo.causal else ki


def _q_major_specs(geo: _Geo):
    """Block specs of the grid (b, h, qi, ki): q-side and kv-side blocks
    of [B, S, H*D] arrays, and a row vector of a [B*Hq, 1, Sq] array."""
    q = pl.BlockSpec((None, geo.bq, geo.d), lambda b, h, qi, ki: (b, qi, h))
    kv = pl.BlockSpec((None, geo.bk, geo.d), lambda b, h, qi, ki: (
        b, _kv_block(geo, qi, ki), h // geo.g))
    row = pl.BlockSpec((None, 1, geo.bq), lambda b, h, qi, ki: (
        b * geo.hq + h, 0, qi))
    return q, kv, row


def _forward(geo: _Geo, q, k, v) -> Tuple[jax.Array, jax.Array]:
    """q: [B,Sq,Hq*D]; k,v: [B,Skv,Hkv*D] -> (o like q, lse [B*Hq,1,Sq])."""
    B, Sq, _ = q.shape
    q_spec, kv_spec, row_spec = _q_major_specs(geo)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, geo=geo),
        grid=(B, geo.hq, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B * geo.hq, 1, Sq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((geo.bq, LANES), jnp.float32),
                        pltpu.VMEM((geo.bq, LANES), jnp.float32),
                        pltpu.VMEM((geo.bq, geo.d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="flash_fwd",
    )(q, k, v)


def _backward(geo: _Geo, q, k, v, o, lse, do):
    """Gradients w.r.t. the unscaled q, k and v; ``q`` is the scaled q."""
    B, Sq, _ = q.shape
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32))
                    .reshape(B, Sq, geo.hq, geo.d), axis=-1)
    delta = jnp.swapaxes(delta, 1, 2).reshape(B * geo.hq, 1, Sq)

    q_spec, kv_spec, row_spec = _q_major_specs(geo)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, geo=geo),
        grid=(B, geo.hq, geo.nq, geo.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((geo.bq, geo.d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta)

    # grid (b, kv head, ki, j): j runs over the G q heads' q blocks
    def q_block(ki, j):
        qi = j % geo.nq
        return jnp.maximum(qi, geo.first_q(ki)) if geo.causal else qi

    q_spec = pl.BlockSpec((None, geo.bq, geo.d), lambda b, h, ki, j: (
        b, q_block(ki, j), h * geo.g + j // geo.nq))
    kv_spec = pl.BlockSpec((None, geo.bk, geo.d),
                           lambda b, h, ki, j: (b, ki, h))
    row_spec = pl.BlockSpec((None, 1, geo.bq), lambda b, h, ki, j: (
        b * geo.hq + h * geo.g + j // geo.nq, 0, q_block(ki, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, geo=geo),
        grid=(B, geo.hkv, geo.nk, geo.g * geo.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((geo.bk, geo.d), jnp.float32),
                        pltpu.VMEM((geo.bk, geo.d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=geo.interpret,
        name="flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _scaled(geo: _Geo, q):
    return (q.astype(jnp.float32) * geo.scale).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _attention(geo: _Geo, q, k, v):
    return _forward(geo, _scaled(geo, q), k, v)[0]


def _attention_fwd(geo: _Geo, q, k, v):
    qs = _scaled(geo, q)
    o, lse = _forward(geo, qs, k, v)
    return o, (qs, k, v, o, lse)


def _attention_bwd(geo: _Geo, res, do):
    return _backward(geo, *res, do)


_attention.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: bool = False) -> jax.Array:
    """q: [B,Sq,Hq,D]; k,v: [B,Skv,Hkv,D] -> [B,Sq,Hq,D], differentiable.

    Query row i sits at position ``q_offset + i`` for the causal mask.
    Blocks default to :func:`pick_block` of each length; a block over 128
    rows must be a multiple of 128, so a length over 128 that none of
    :data:`BLOCKS` divides raises.  On the TPU the head dim must be a
    multiple of 128 (a block is a lane-aligned slice of H*D)."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    bq = min(block_q or pick_block(Sq), Sq)
    bk = min(block_k or pick_block(Skv), Skv)
    if Sq % bq or Skv % bk or Hq % Hkv:
        raise ValueError(f"blocks {bq}/{bk} do not tile Sq={Sq}/Skv={Skv}, "
                         f"or {Hq} q heads do not group over {Hkv}")
    if any(b > LANES and b % LANES for b in (bq, bk)):
        raise ValueError(f"blocks {bq}/{bk} for Sq={Sq}/Skv={Skv}: a block "
                         f"over {LANES} rows must be a multiple of {LANES}; "
                         f"no block of {BLOCKS} divides such a length")
    geo = _Geo(causal=causal, q_offset=q_offset, bq=bq, bk=bk,
               nq=Sq // bq, nk=Skv // bk, hq=Hq, hkv=Hkv, d=D,
               interpret=interpret)
    o = _attention(geo, q.reshape(B, Sq, Hq * D), k.reshape(B, Skv, Hkv * D),
                   v.reshape(B, Skv, Hkv * D))
    return o.reshape(B, Sq, Hq, D)

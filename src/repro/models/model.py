"""Top-level model API: init / train forward / prefill / decode, per family.

All depth is expressed as ``jax.lax.scan`` over stacked layer parameters so
the lowered HLO contains exactly one block body (plus remat policy), which
keeps 512-device compiles tractable and gives XLA a single loop to overlap
collectives around.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import (
    ModelConfig, DENSE, MOE, HYBRID, SSM, ENCDEC, VLM,
)
from repro.models import layers as L
from repro.models import blocks as B
from repro.models import hybrid as HY
from repro.models import rwkv6 as RW
from repro.models import encdec as ED
from repro.parallel.context import shard

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(init_fn, key, n: int) -> Params:
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _stack_axes(axes: Any) -> Any:
    return jax.tree.map(lambda ax: ("layers",) + ax, axes,
                        is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    ke, kb, kf = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "embed": L.embedding_init(cfg, ke),
        "final_norm": L.rmsnorm_init(cfg.d_model, dt),
    }
    if cfg.family in (DENSE, MOE, VLM):
        p["blocks"] = _stack_init(lambda k: B.block_init(cfg, k), kb,
                                  cfg.num_layers)
    elif cfg.family == HYBRID:
        nb = cfg.num_layers // cfg.hybrid_period
        p["blocks"] = _stack_init(lambda k: HY.superblock_init(cfg, k), kb, nb)
    elif cfg.family == SSM:
        p["blocks"] = _stack_init(lambda k: RW.rwkv_init(cfg, k), kb,
                                  cfg.num_layers)
    elif cfg.family == ENCDEC:
        p["enc_blocks"] = _stack_init(lambda k: ED.enc_block_init(cfg, k),
                                      kb, cfg.encoder_layers)
        p["dec_blocks"] = _stack_init(lambda k: ED.dec_block_init(cfg, k),
                                      kf, cfg.decoder_layers)
        p["enc_norm"] = L.rmsnorm_init(cfg.d_model, dt)
    else:
        raise ValueError(cfg.family)
    return p


def param_axes(cfg: ModelConfig) -> Any:
    a: Dict[str, Any] = {
        "embed": L.embedding_axes(cfg),
        "final_norm": ("embed",),
    }
    if cfg.family in (DENSE, MOE, VLM):
        a["blocks"] = _stack_axes(B.block_axes(cfg))
    elif cfg.family == HYBRID:
        a["blocks"] = _stack_axes(HY.superblock_axes(cfg))
    elif cfg.family == SSM:
        a["blocks"] = _stack_axes(RW.rwkv_axes(cfg))
    elif cfg.family == ENCDEC:
        a["enc_blocks"] = _stack_axes(ED.enc_block_axes(cfg))
        a["dec_blocks"] = _stack_axes(ED.dec_block_axes(cfg))
        a["enc_norm"] = ("embed",)
    return a


# ---------------------------------------------------------------------------
# scan helpers
# ---------------------------------------------------------------------------

def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def _scan_blocks(cfg: ModelConfig, blocks: Params, h: jax.Array,
                 positions: jax.Array, apply_fn) -> Tuple[jax.Array, jax.Array]:
    """Scan ``apply_fn(params_i, h) -> (h, aux)`` over stacked blocks.

    With ``cfg.layers_per_step = g > 1`` the stacked params are regrouped
    [L, ...] -> [L/g, g, ...] and each scan step applies g layers inside a
    single remat region: the per-layer carry stash (the dominant training
    memory term for deep dense models, EXPERIMENTS.md §Perf) shrinks g-fold
    at the cost of recomputing g layers in backward.
    """
    g = max(cfg.layers_per_step, 1)

    def body(carry, layer_params):
        h, aux = carry
        h = shard(h, "batch", None, "embed_act")
        if g == 1:
            h, a = apply_fn(layer_params, h, positions)
            aux = aux + a
        else:
            for i in range(g):
                lp = jax.tree.map(lambda x: x[i], layer_params)
                h, a = apply_fn(lp, h, positions)
                aux = aux + a
        return (h, aux), None

    if g > 1:
        L_ = next(iter(jax.tree.leaves(blocks))).shape[0]
        assert L_ % g == 0, (L_, g)
        blocks = jax.tree.map(
            lambda x: x.reshape(L_ // g, g, *x.shape[1:]), blocks)

    body = _maybe_remat(cfg, body)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), blocks)
    return h, aux


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ModelConfig, p: Params, batch: Batch) -> jax.Array:
    """Token embeddings, with modality-frontend embeddings prepended."""
    h = L.embed_tokens(cfg, p["embed"], batch["tokens"])
    if cfg.frontend_embed_dim and "frontend" in batch:
        f = jnp.einsum("bse,ed->bsd", batch["frontend"].astype(cfg.dtype),
                       p["embed"]["frontend_proj"].astype(cfg.dtype))
        h = jnp.concatenate([f, h], axis=1)
    return shard(h, "batch", None, "embed_act")


def _logits(cfg: ModelConfig, p: Params, h: jax.Array) -> jax.Array:
    with jax.named_scope("head"):
        h = L.rmsnorm(h, p["final_norm"], cfg.rms_eps)
        logits = L.unembed(cfg, p["embed"], h)
        return shard(logits, "batch", None, "vocab_act")


# ---------------------------------------------------------------------------
# full-sequence forward (train / eval / prefill base)
# ---------------------------------------------------------------------------

def forward(cfg: ModelConfig, p: Params, batch: Batch,
            ) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits [B,S,V], aux_loss)."""
    positions = batch["positions"]
    if cfg.family in (DENSE, MOE, VLM):
        h = _embed_inputs(cfg, p, batch)
        apply_fn = lambda lp, hh, pos: B.block_apply(cfg, lp, hh, pos)
        h, aux = _scan_blocks(cfg, p["blocks"], h, positions, apply_fn)
    elif cfg.family == HYBRID:
        h = _embed_inputs(cfg, p, batch)
        apply_fn = lambda lp, hh, pos: HY.superblock_apply(cfg, lp, hh, pos)
        h, aux = _scan_blocks(cfg, p["blocks"], h, positions, apply_fn)
    elif cfg.family == SSM:
        h = _embed_inputs(cfg, p, batch)

        def rwkv_apply(lp, hh, pos):
            del pos
            xn = L.rmsnorm(hh, lp["ln1"], cfg.rms_eps)
            xprev = jnp.pad(xn, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
            hh = hh + RW.rwkv_time_mix(cfg, lp, xn, xprev)
            xn = L.rmsnorm(hh, lp["ln2"], cfg.rms_eps)
            xprev = jnp.pad(xn, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
            hh = hh + RW.rwkv_channel_mix(cfg, lp, xn, xprev)
            return hh, jnp.zeros((), jnp.float32)

        h, aux = _scan_blocks(cfg, p["blocks"], h, positions, rwkv_apply)
    elif cfg.family == ENCDEC:
        enc_h, enc_positions = _encode(cfg, p, batch)
        h = L.embed_tokens(cfg, p["embed"], batch["tokens"])
        h = shard(h, "batch", None, "embed_act")

        def dec_apply(lp, hh, pos):
            hh = ED.dec_block_apply(cfg, lp, hh, pos, enc_h, enc_positions)
            return hh, jnp.zeros((), jnp.float32)

        h, aux = _scan_blocks(cfg, p["dec_blocks"], h, positions, dec_apply)
    else:
        raise ValueError(cfg.family)
    return _logits(cfg, p, h), aux


def _encode(cfg: ModelConfig, p: Params, batch: Batch,
            ) -> Tuple[jax.Array, jax.Array]:
    f = batch["frontend"].astype(cfg.dtype)
    enc_h = jnp.einsum("bse,ed->bsd", f,
                       p["embed"]["frontend_proj"].astype(cfg.dtype))
    enc_h = shard(enc_h, "batch", None, "embed_act")
    Bsz, Senc = enc_h.shape[:2]
    enc_positions = jnp.broadcast_to(jnp.arange(Senc)[None, :], (Bsz, Senc))

    def enc_apply(lp, hh, pos):
        return ED.enc_block_apply(cfg, lp, hh, pos), jnp.zeros((), jnp.float32)

    enc_h, _ = _scan_blocks(cfg, p["enc_blocks"], enc_h, enc_positions,
                            enc_apply)
    return L.rmsnorm(enc_h, p["enc_norm"], cfg.rms_eps), enc_positions


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

Z_LOSS_COEF = 1e-4


def loss_fn(cfg: ModelConfig, p: Params, batch: Batch,
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(cfg, p, batch)
    with jax.named_scope("head"):      # the loss; _logits holds the rest
        targets = batch["targets"]
        V = cfg.vocab_size
        if (cfg.frontend_embed_dim and "frontend" in batch
                and cfg.family != ENCDEC):
            # frontend positions carry no next-token target; score the text
            # tail only
            S_text = targets.shape[1]
            logits = logits[:, -S_text:, :]
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        mask = (targets >= 0).astype(jnp.float32)
        tgt = jnp.where(targets >= 0, targets, 0)
        # target log-prob via a one-hot masked reduction rather than a gather:
        # GSPMD partitions select+reduce along the (model-sharded) vocab dim,
        # while a take_along_axis gather forces an involuntary all-gather of
        # the [B,S,V] logits on every device (measured +10 GB/device on the
        # 152k-vocab archs — see EXPERIMENTS.md §Perf iteration 1).
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape,
                                              lf.ndim - 1)
        ll = jnp.sum(jnp.where(vocab_iota == tgt[..., None], lf, 0.0),
                     axis=-1)
        nll = (lse - ll) * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        ce = jnp.sum(nll) / denom
        z = Z_LOSS_COEF * jnp.sum(jnp.square(lse) * mask) / denom
        total = ce + z + aux
        return total, {"loss": total, "ce": ce, "aux": aux, "z": z,
                       "tokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# KV-cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    if cfg.family in (DENSE, MOE, VLM):
        Lc = cfg.num_layers
        c = {"k": jnp.zeros((Lc, batch, max_len, cfg.kv_dim), dt),
             "v": jnp.zeros((Lc, batch, max_len, cfg.kv_dim), dt)}
    elif cfg.family == HYBRID:
        c = HY.hybrid_cache_init(cfg, batch, max_len)
    elif cfg.family == SSM:
        one = RW.rwkv_cache_init(cfg, batch)
        c = {k: jnp.zeros((cfg.num_layers,) + v.shape, v.dtype)
             for k, v in one.items()}
    elif cfg.family == ENCDEC:
        Ld = cfg.decoder_layers
        c = {"k": jnp.zeros((Ld, batch, max_len, cfg.kv_dim), dt),
             "v": jnp.zeros((Ld, batch, max_len, cfg.kv_dim), dt),
             "xk": jnp.zeros((Ld, batch, max_len, cfg.kv_dim), dt),
             "xv": jnp.zeros((Ld, batch, max_len, cfg.kv_dim), dt)}
    else:
        raise ValueError(cfg.family)
    c["index"] = jnp.zeros((batch,), jnp.int32)
    return c


def cache_logical_axes(cfg: ModelConfig, *, shard_seq: bool = False) -> Any:
    """Logical axes for the cache pytree (seq axis shardable for long ctx)."""
    seq = "kv_seq" if shard_seq else None
    if cfg.family in (DENSE, MOE, VLM):
        a = {"k": (None, "batch", seq, "kv_act"),
             "v": (None, "batch", seq, "kv_act")}
    elif cfg.family == HYBRID:
        a = {"k": (None, "batch", seq, "kv_act"),
             "v": (None, "batch", seq, "kv_act"),
             "conv": (None, None, "batch", None, "inner_act"),
             "ssm": (None, None, "batch", "inner_act", None)}
    elif cfg.family == SSM:
        a = {"tshift": (None, "batch", "embed_act"),
             "cshift": (None, "batch", "embed_act"),
             "wkv": (None, "batch", "heads_act", None, None)}
    elif cfg.family == ENCDEC:
        a = {"k": (None, "batch", seq, "kv_act"),
             "v": (None, "batch", seq, "kv_act"),
             "xk": (None, "batch", seq, "kv_act"),
             "xv": (None, "batch", seq, "kv_act")}
    else:
        raise ValueError(cfg.family)
    a["index"] = ("batch",)
    return a


def prefill(cfg: ModelConfig, p: Params, batch: Batch, max_len: int,
            ) -> Tuple[jax.Array, Params]:
    """Run the full prompt; returns (last-position logits, filled cache)."""
    positions = batch["positions"]

    if cfg.family in (DENSE, MOE, VLM):
        h = _embed_inputs(cfg, p, batch)

        def body(carry, lp):
            hh = carry
            hh = shard(hh, "batch", None, "embed_act")
            hh, kv, _ = B.block_prefill(cfg, lp, hh, positions)
            return hh, kv

        body = _maybe_remat(cfg, body)
        h, kvs = jax.lax.scan(body, h, p["blocks"])
        cache = _embed_cache(cfg, kvs, h.shape[0], max_len)
    elif cfg.family == HYBRID:
        h = _embed_inputs(cfg, p, batch)

        def body(carry, lp):
            hh = carry
            hh, kv, _ = HY.superblock_prefill(cfg, lp, hh, positions)
            return hh, kv

        h, kvs = jax.lax.scan(body, h, p["blocks"])
        cache = _embed_cache(cfg, {"k": kvs["k"], "v": kvs["v"]},
                             h.shape[0], max_len)
        cache["conv"] = kvs["conv"].astype(cfg.dtype)
        cache["ssm"] = kvs["ssm"]
    elif cfg.family == SSM:
        h = _embed_inputs(cfg, p, batch)

        def body(carry, lp):
            hh = carry
            xn = L.rmsnorm(hh, lp["ln1"], cfg.rms_eps)
            xprev = jnp.pad(xn, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
            tm, st = RW.rwkv_time_mix(cfg, lp, xn, xprev, return_state=True)
            hh = hh + tm
            xn2 = L.rmsnorm(hh, lp["ln2"], cfg.rms_eps)
            xprev2 = jnp.pad(xn2, ((0, 0), (1, 0), (0, 0)))[:, :-1, :]
            hh = hh + RW.rwkv_channel_mix(cfg, lp, xn2, xprev2)
            ent = {"tshift": xn[:, -1, :], "cshift": xn2[:, -1, :],
                   "wkv": st}
            return hh, ent

        h, kvs = jax.lax.scan(body, h, p["blocks"])
        cache = dict(kvs)
    elif cfg.family == ENCDEC:
        enc_h, enc_positions = _encode(cfg, p, batch)
        h = L.embed_tokens(cfg, p["embed"], batch["tokens"])

        def body(carry, lp):
            hh = carry
            hh, kv = ED.dec_block_prefill(cfg, lp, hh, positions, enc_h,
                                          enc_positions)
            return hh, kv

        h, kvs = jax.lax.scan(body, h, p["dec_blocks"])
        cache = _embed_cache(cfg, {"k": kvs["k"], "v": kvs["v"]},
                             h.shape[0], max_len)
        cache["xk"] = kvs["xk"]
        cache["xv"] = kvs["xv"]
    else:
        raise ValueError(cfg.family)

    prefilled = batch["tokens"].shape[1]
    if (cfg.frontend_embed_dim and "frontend" in batch
            and cfg.family != ENCDEC):
        prefilled += batch["frontend"].shape[1]
    Bsz = batch["tokens"].shape[0]
    cache["index"] = jnp.full((Bsz,), prefilled, jnp.int32)
    logits = _logits(cfg, p, h[:, -1:, :])
    return logits, cache


def _embed_cache(cfg: ModelConfig, kvs: Dict[str, jax.Array], batch: int,
                 max_len: int) -> Params:
    """Pad prefill K/V [L,B,S,kv] into a [L,B,max_len,kv] decode cache."""
    out = {}
    for name in ("k", "v"):
        t = kvs[name].astype(cfg.dtype)
        S = t.shape[2]
        pad = max_len - S
        out[name] = jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return out


def decode_step(cfg: ModelConfig, p: Params, tokens: jax.Array,
                cache: Params) -> Tuple[jax.Array, Params]:
    """One-token decode.  tokens: [B,1] -> (logits [B,1,V], new cache)."""
    index = cache["index"]
    h = L.embed_tokens(cfg, p["embed"], tokens)
    h = shard(h, "batch", None, "embed_act")
    new_cache = dict(cache)

    if cfg.family in (DENSE, MOE, VLM):
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(index[None, :, None],
                                   (3, tokens.shape[0], 1)).astype(jnp.int32)
        else:
            pos = index[:, None]

        def body(carry, xs):
            hh = carry
            lp, ck, cv = xs
            hh = shard(hh, "batch", None, "embed_act")
            hh, ck, cv = B.block_decode(cfg, lp, hh, pos, ck, cv, index)
            return hh, (ck, cv)

        h, (ks, vs) = jax.lax.scan(body, h, (p["blocks"], cache["k"],
                                             cache["v"]))
        new_cache["k"], new_cache["v"] = ks, vs
    elif cfg.family == HYBRID:
        pos = index[:, None]

        def body(carry, xs):
            hh = carry
            lp, ce = xs
            hh, ce = HY.superblock_decode(cfg, lp, hh, pos, ce, index)
            return hh, ce

        sub = {k: cache[k] for k in ("k", "v", "conv", "ssm")}
        h, sub = jax.lax.scan(body, h, (p["blocks"], sub))
        new_cache.update(sub)
    elif cfg.family == SSM:

        def body(carry, xs):
            hh = carry
            lp, ce = xs
            xn = L.rmsnorm(hh, lp["ln1"], cfg.rms_eps)
            tm, st = RW.rwkv_decode_time(cfg, lp, xn, ce)
            hh = hh + tm
            xn2 = L.rmsnorm(hh, lp["ln2"], cfg.rms_eps)
            cm, cshift = RW.rwkv_decode_channel(cfg, lp, xn2, ce["cshift"])
            hh = hh + cm
            st["cshift"] = cshift
            return hh, st

        sub = {k: cache[k] for k in ("tshift", "cshift", "wkv")}
        h, sub = jax.lax.scan(body, h, (p["blocks"], sub))
        new_cache.update(sub)
    elif cfg.family == ENCDEC:
        pos = index[:, None]

        def body(carry, xs):
            hh = carry
            lp, ce = xs
            hh, ce = ED.dec_block_decode(cfg, lp, hh, pos, ce, index)
            return hh, ce

        sub = {k: cache[k] for k in ("k", "v", "xk", "xv")}
        h, sub = jax.lax.scan(body, h, (p["dec_blocks"], sub))
        new_cache.update(sub)
    else:
        raise ValueError(cfg.family)

    new_cache["index"] = index + 1
    return _logits(cfg, p, h), new_cache

"""The plain reference: a decoder's forward and backward, and two AdamW
steps.

The layers are the architecture module's (``bench/archs/<model_type>.py``,
written from the published architecture, through :func:`bench.model.arch`);
this module holds what every decoder shares: the matrix products in each
precision, RMSNorm, rotary positions and causal attention for the layers
to use, the embedding, the tied or untied head with its loss, the pass
over the layer stacks, and the optimiser as a cell's traffic file sets
it.  It imports nothing of the program and takes no array the program
made: the weights come from :mod:`bench.model` and the seed, the token
stream from :mod:`bench.corpus`.

Arithmetic is float32 with ``Precision.HIGHEST`` matrix products.  The
``fp8`` variant rounds both operands of every matrix product to
float8_e4m3 with one scale per tensor: it is the control, the reference
put one precision below the bfloat16 the configurations state.

Training is layer by layer (a forward pass that keeps each layer's input,
then one ``vjp`` per layer), and the loss head runs in blocks of rows, so
that a whole step at seq 4096 fits one chip.  A layer may return a term
the loss adds (a router's balance loss); its gradient flows back through
that layer's ``vjp``.  Two departures from the
published description are the program's objective, followed here on
purpose: the loss adds ``1e-4 * mean(logsumexp**2)`` (a z-loss), and the
learning rate follows a linear warm-up from 0 and a cosine decay to a
tenth of it, as the program's schedule fixes it.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import model

HIGHEST = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
ATTN_BLOCK = 512      # query rows per attention block
HEAD_BLOCK = 512      # rows per block of the loss head


def _dot_f32(eq: str, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round8(x, dtype):
    """x rounded to a float8 format, with one scale for the whole tensor."""
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dot_fp8(eq: str, a, b):
    return _dot_f32(eq, _round8(f32(a), jnp.float8_e4m3fn),
                    _round8(f32(b), jnp.float8_e4m3fn))


def _dot_fp8_fwd(eq, a, b):
    ra = _round8(f32(a), jnp.float8_e4m3fn)
    rb = _round8(f32(b), jnp.float8_e4m3fn)
    return _dot_f32(eq, ra, rb), (ra, rb)


def _dot_fp8_bwd(eq, res, g):
    """Products of the backward pass take the float8-rounded operands and
    the cotangent rounded to float8_e5m2, as float8 training does.
    (Every operand here is float32, so are the gradients.)"""
    _, pull = jax.vjp(lambda x, y: _dot_f32(eq, x, y), *res)
    return pull(_round8(g, jnp.float8_e5m2))


_dot_fp8.defvjp(_dot_fp8_fwd, _dot_fp8_bwd)


DOTS: Dict[str, Callable] = {"f32": _dot_f32, "fp8": _dot_fp8}


def f32(x):
    return x.astype(jnp.float32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(w)


def attend(dot, q, k, v):
    """Causal softmax attention in blocks of query rows; q [B,S,H,Dq],
    k [B,S,K,Dq] and v [B,S,K,Dv] with K dividing H (grouped queries)."""
    B, S, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    q = q.reshape(B, S, K, G, Dh)
    outs = []
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(qb, start):
        s = dot("bqkgd,bskd->bkgqs", qb, k) * Dh ** -0.5
        qpos = start + jnp.arange(qb.shape[1])
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return dot("bkgqs,bskd->bqkgd", w, v)

    for start in range(0, S, ATTN_BLOCK):
        outs.append(block(q[:, start:start + ATTN_BLOCK], start))
    return jnp.concatenate(outs, 1).reshape(B, S, H * v.shape[-1])


def rope(x, pos, theta):
    """x [B,S,H,Dh] rotated by positions [B,S] (rotate-half convention)."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """Jitted pieces of the reference for one configuration and precision."""

    def __init__(self, cfg: Dict[str, Any], precision: str = "f32"):
        self.cfg = cfg
        self.arch = model.arch(cfg)
        self.order = model.layer_stacks(cfg)
        self.dot = DOTS[precision]
        self.eps = cfg["rms_norm_eps"]
        self.fwd_layer = jax.jit(self._layer, static_argnums=(0,))
        self.bwd_layer = jax.jit(self._layer_vjp, static_argnums=(0,))
        self.head_grad = jax.jit(self._head_grad, donate_argnums=(0, 1, 2))
        self.head_loss = jax.jit(self._head_loss)
        self.embed_grad = jax.jit(
            lambda acc, tok, dh: acc.at[tok.reshape(-1)].add(
                dh.reshape(-1, dh.shape[-1])), donate_argnums=(0,))
        self.logits = jax.jit(self._logits)

    # ---- one layer -------------------------------------------------------
    def _layer(self, stack, lp, h, pos):
        return self.arch.layer(self.cfg, stack, self.dot, lp, h, pos)

    def _layer_vjp(self, stack, lp, h, pos, dh):
        out, pull = jax.vjp(lambda p, x: self._layer(stack, p, x, pos), lp, h)
        if isinstance(out, tuple):       # the layer's loss term, added once
            return pull((dh, jnp.ones_like(out[1])))
        return pull(dh)

    # ---- head and loss ---------------------------------------------------
    def _head_w(self, top):
        if "unembed" in top:
            return f32(top["unembed"]), "bsd,dv->bsv"
        return f32(top["embedding"]), "bsd,vd->bsv"

    def _rows_loss(self, top, h, tgt, mask, denom):
        """This block's share of the loss: sum over its rows / denom."""
        w, eq = self._head_w(top)
        logits = self.dot(eq, rmsnorm(h, top["final_norm"], self.eps), w)
        lse = jax.nn.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
        return jnp.sum(((lse - ll) + Z_LOSS * lse * lse) * mask) / denom

    def _head_grad(self, acc_w, acc_norm, acc_loss, top, h, tgt, mask,
                   denom):
        loss, pull = jax.vjp(
            lambda t, x: self._rows_loss(t, x, tgt, mask, denom), top, h)
        dtop, dh = pull(jnp.ones((), jnp.float32))
        key = "unembed" if "unembed" in top else "embedding"
        return (acc_w + dtop[key], acc_norm + dtop["final_norm"],
                acc_loss + loss, dh)

    def _head_loss(self, top, h, tgt, mask, denom):
        return self._rows_loss(top, h, tgt, mask, denom)

    def _logits(self, top, h):
        w, eq = self._head_w(top)
        return self.dot(eq, rmsnorm(h, top["final_norm"], self.eps), w)

    # ---- whole passes ----------------------------------------------------
    def embed(self, top, tokens):
        return f32(jnp.take(top["embedding"], tokens, axis=0))

    def forward_hidden(self, top, layers, tokens, pos):
        """The hidden state entering each layer and leaving the last, and
        the sum of the layers' loss terms."""
        h = self.embed(top, tokens)
        hs, extra = [h], 0.0
        for stack, lp in zip(self.order, layers):
            h = self.fwd_layer(stack, lp, h, pos)
            if isinstance(h, tuple):
                h, term = h
                extra += float(term)
            hs.append(h)
        return hs, extra

    def loss(self, top, layers, batch) -> float:
        tokens, tgt, mask = batch
        pos = _positions(tokens)
        hs, total = self.forward_hidden(top, layers, tokens, pos)
        h = hs[-1]
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        for s in range(0, tokens.shape[1], HEAD_BLOCK):
            sl = slice(s, s + HEAD_BLOCK)
            total += float(self.head_loss(top, h[:, sl], tgt[:, sl],
                                          mask[:, sl], denom))
        return total

    def grads(self, top, layers, batch, on_layer: Callable[[int, Any], None]):
        """Loss and gradients of one batch; each layer's gradient is handed
        to ``on_layer(index, grads)`` as soon as it exists.  Returns
        (loss, gradients of the top-level weights)."""
        tokens, tgt, mask = batch
        pos = _positions(tokens)
        hs, extra = self.forward_hidden(top, layers, tokens, pos)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        key = "unembed" if "unembed" in top else "embedding"
        acc_w = jnp.zeros(top[key].shape, jnp.float32)
        acc_n = jnp.zeros(top["final_norm"].shape, jnp.float32)
        acc_l = jnp.zeros((), jnp.float32)
        dhs = []
        for s in range(0, tokens.shape[1], HEAD_BLOCK):
            sl = slice(s, s + HEAD_BLOCK)
            acc_w, acc_n, acc_l, dh = self.head_grad(
                acc_w, acc_n, acc_l, top, hs[-1][:, sl], tgt[:, sl],
                mask[:, sl], denom)
            dhs.append(dh)
        dh = jnp.concatenate(dhs, 1)
        for i in reversed(range(len(layers))):
            dlp, dh = self.bwd_layer(self.order[i], layers[i], hs[i], pos,
                                     dh)
            hs[i + 1] = None
            on_layer(i, dlp)
        g_top = {"final_norm": acc_n}
        if key == "unembed":
            g_top["unembed"] = acc_w
            acc_w = jnp.zeros(top["embedding"].shape, jnp.float32)
        g_top["embedding"] = self.embed_grad(acc_w, tokens, dh)
        return float(acc_l) + extra, g_top


def _positions(tokens):
    return jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                            tokens.shape)


# ---------------------------------------------------------------------------
# weights in the reference's layout: top-level leaves + one dict per layer,
# the layers of every stack in the order they run
# ---------------------------------------------------------------------------

def split_layers(cfg, flat: List[Any]):
    """Flat leaves (``model.leaf_specs`` order, layers stacked) -> (top,
    layers).  Each stacked leaf is freed once it is cut into layers."""
    order = model.layer_stacks(cfg)
    top: Dict[str, Any] = {}
    layers: List[Dict[str, Any]] = [dict() for _ in order]
    for (path, _, _, stack), leaf in zip(model.leaf_specs(cfg), flat):
        if stack is None:
            top[path[-1]] = leaf
            continue
        for i, lp in enumerate(_of_stack(order, layers, stack)):
            node = lp
            for k in path[1:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = leaf[i]
        leaf.delete()
    return top, layers


def _of_stack(order: List[str], layers: List[Any], stack: str) -> List[Any]:
    """The layers of one stack, in order."""
    return [lp for s, lp in zip(order, layers) if s == stack]


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def leaf_norms(cfg, top, layers) -> List[float]:
    """Norm of each leaf of a reference-layout tree, ``leaf_specs`` order."""
    order = model.layer_stacks(cfg)
    out = []
    for path, _, _, stack in model.leaf_specs(cfg):
        if stack is None:
            out.append(float(_norm(top[path[-1]])))
        else:
            out.append(math.sqrt(sum(float(_norm(_get(lp, path[1:]))) ** 2
                                     for lp in _of_stack(order, layers,
                                                         stack))))
    return out


_norm = jax.jit(lambda x: jnp.sqrt(jnp.sum(jnp.square(f32(x)))))


# ---------------------------------------------------------------------------
# two AdamW steps
# ---------------------------------------------------------------------------

# The program's published schedule decays to this share of ``lr`` (its
# ``optim/schedule.py`` fixes it; no setting changes it).
LR_FLOOR = 0.1


def lr_at(count: int, opt: Dict[str, Any]) -> float:
    """The schedule: linear warm-up from 0 over ``warmup_steps``, then a
    cosine from ``lr`` down to ``LR_FLOOR * lr`` at ``total_steps``;
    ``count`` is the number of updates already made."""
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * frac))
    return opt["lr"] * warm * (LR_FLOOR + (1 - LR_FLOOR) * cos)


@functools.partial(jax.jit, static_argnames=("t", "b1", "b2", "eps"))
def _adam(p, g1, g2, c1, c2, lr, wd, *, t, b1, b2, eps):
    """The parameter after update ``t`` (1 or 2), from the clipped
    gradients of the updates so far; stored in the weights' dtype."""
    g1 = g1 * c1
    m = (1 - b1) * g1
    v = (1 - b2) * g1 * g1
    if t == 2:
        g2 = g2 * c2
        m = b1 * m + (1 - b1) * g2
        v = b2 * v + (1 - b2) * g2 * g2
    u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    pf = f32(p)
    return (pf - lr * (u + wd * pf)).astype(p.dtype)


def _wd(opt, ndim: int) -> float:
    """Weight decay on matrices only, by the rank of one layer's leaf."""
    return opt["weight_decay"] if ndim >= 2 else 0.0


def train_reference(cfg: Dict[str, Any], seed: int, batches, opt: Dict,
                    precision: str = "f32", rows: str = "all") -> Dict:
    """Losses of steps 1-3, the clipped first gradient's leaf norms, and
    the leaf norms of the weights' change over updates 1-2 (what step 3
    computes with).  ``rows="half"`` plants a fault: the loss is the mean
    over the first half of each sequence only."""
    ref = Reference(cfg, precision)
    specs = model.leaf_specs(cfg)
    batches = [_mask_rows(b, rows) for b in batches]
    top, layers = split_layers(cfg, model.flat_leaves(
        model.make_weights(cfg, seed), cfg))
    kw = dict(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])

    # update 1 ---------------------------------------------------------------
    g1_layers: List[Any] = [None] * len(layers)

    def keep(i, g):
        g1_layers[i] = g

    loss1, g1_top = ref.grads(top, layers, batches[0], keep)
    gn1 = _global_norm(g1_top, g1_layers)
    c1 = min(1.0, opt["grad_clip"] / (gn1 + 1e-9))
    grad_norms = [c1 * n for n in leaf_norms(cfg, g1_top, g1_layers)]
    lr1 = lr_at(0, opt)
    top = {k: _adam(v, g1_top[k], g1_top[k], c1, 0.0, lr1,
                    _wd(opt, v.ndim), t=1, **kw)
           for k, v in top.items()}
    for i, lp in enumerate(layers):
        layers[i] = _map_layer(lp, g1_layers[i], None, lambda p, a, b:
                               _adam(p, a, a, c1, 0.0, lr1, _wd(opt, p.ndim),
                                     t=1, **kw))

    # update 2: one pass for the clipping norm, one that applies it ---------
    sq = [0.0]

    def count(i, g):
        sq[0] += sum(float(_norm(x)) ** 2 for x in jax.tree.leaves(g))

    loss2, g2_top = ref.grads(top, layers, batches[1], count)
    gn2 = math.sqrt(sq[0] + sum(float(_norm(x)) ** 2
                                for x in g2_top.values()))
    c2 = min(1.0, opt["grad_clip"] / (gn2 + 1e-9))
    lr2 = lr_at(1, opt)
    new_layers: List[Any] = [None] * len(layers)

    def apply(i, g):
        new_layers[i] = _map_layer(
            layers[i], g1_layers[i], g, lambda p, a, b: _adam(
                p, a, b, c1, c2, lr2, _wd(opt, p.ndim), t=2, **kw))
        g1_layers[i] = None

    ref.grads(top, layers, batches[1], apply)
    layers = new_layers
    top = {k: _adam(v, g1_top[k], g2_top[k], c1, c2, lr2,
                    _wd(opt, v.ndim), t=2, **kw)
           for k, v in top.items()}
    del g1_top, g2_top

    loss3 = ref.loss(top, layers, batches[2])

    # change of each leaf over the two updates, against the seed's weights:
    # its norm and its sketch (bench.model.sketch, summed over layers)
    key = _root(seed)
    delta, sketches = [], []
    for idx, (path, _, _, stack) in enumerate(specs):
        p0 = jax.jit(lambda k: model.make_leaf(cfg, k, idx))(key)
        if stack is None:
            n, sk = _change(top[path[-1]], p0, 0)
            delta.append(float(n))
            sketches.append(np.asarray(sk))
        else:
            parts = [_change(_get(lp, path[1:]), p0[i], i)
                     for i, lp in enumerate(_of_stack(ref.order, layers,
                                                      stack))]
            delta.append(math.sqrt(sum(float(n) ** 2 for n, _ in parts)))
            sketches.append(np.sum([np.asarray(sk) for _, sk in parts], 0))
        p0.delete()
    return {"losses": [loss1, loss2, loss3], "grad_norms": grad_norms,
            "delta_norms": delta, "delta_sketches": sketches}


@jax.jit
def _change(p, p0, layer):
    d = f32(p) - f32(p0)
    return jnp.sqrt(jnp.sum(d * d)), model.sketch(d, layer)


def _root(seed):
    from bench.common import root_key
    return root_key(seed)


def _mask_rows(batch, rows):
    tokens, tgt, mask = batch
    if rows == "half":
        S = tokens.shape[1]
        mask = mask * (jnp.arange(S) < S // 2)[None, :].astype(mask.dtype)
    return tokens, tgt, mask


def _map_layer(lp, ga, gb, fn):
    if isinstance(lp, dict):
        return {k: _map_layer(lp[k], ga[k], None if gb is None else gb[k],
                              fn) for k in lp}
    return fn(lp, ga, gb if gb is not None else ga)


def _global_norm(top, layers) -> float:
    sq = sum(float(_norm(x)) ** 2 for x in top.values())
    for g in layers:
        sq += sum(float(_norm(x)) ** 2 for x in jax.tree.leaves(g))
    return math.sqrt(sq)


# ---------------------------------------------------------------------------
# serving: logits of a whole sequence, for the gap of each served token
# ---------------------------------------------------------------------------

def sequence_logits(ref: Reference, top, layers, tokens: np.ndarray):
    """Logits [S, V] (float32) of one sequence under the reference."""
    t = jnp.asarray(tokens, jnp.int32)[None]
    pos = _positions(t)
    h = ref.forward_hidden(top, layers, t, pos)[0][-1]
    return ref.logits(top, h)[0]

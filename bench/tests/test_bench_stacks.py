"""The reference's pass over layer stacks, layer by layer, against
``jax.grad`` of the same whole model written in one function: a toy
architecture (``toy_arch.py``) with two stacks of different layers, one
returning a loss term, and a leaf that starts at zero."""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import model, reference
from bench.tests import toy_arch

HIGHEST = jax.lax.Precision.HIGHEST
OPT = {"lr": 1e-2, "warmup_steps": 1, "total_steps": 100,
       "weight_decay": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "grad_clip": 1.0}
SEED = 2 ** 31 + 777
B, S = 2, 24
TOL = 1e-5


@pytest.fixture
def cfg(monkeypatch):
    monkeypatch.setitem(sys.modules, "bench.archs.toy", toy_arch)
    return dict(toy_arch.CONFIG)


def _batches(cfg):
    key = jax.random.key(7)
    out = []
    for j in range(3):
        t = jax.random.randint(jax.random.fold_in(key, j), (B, S + 1), 0,
                               cfg["vocab_size"])
        out.append((t[:, :-1], t[:, 1:], jnp.ones((B, S), jnp.float32)))
    return out


def _whole_loss(cfg, params, batch):
    """Every layer of every stack, the untied head, the cross-entropy with
    its z-loss over the masked rows, and the layers' loss terms."""
    tokens, tgt, mask = batch
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), tokens.shape)
    h = params["embed"]["embedding"][tokens]
    extra = 0.0
    for stack, n in toy_arch.stacks(cfg):
        for i in range(n):
            lp = jax.tree.map(lambda x: x[i], params[stack])
            out = toy_arch.layer(cfg, stack, reference.DOTS["f32"], lp, h,
                                 pos)
            h, term = out if isinstance(out, tuple) else (out, 0.0)
            extra = extra + term
    x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * params["final_norm"]
    logits = jnp.einsum("bsd,dv->bsv", x, params["embed"]["unembed"],
                        precision=HIGHEST)
    lse = jax.nn.logsumexp(logits, -1)
    ll = jnp.take_along_axis(logits, tgt[..., None], -1)[..., 0]
    ce = jnp.sum(((lse - ll) + 1e-4 * lse * lse) * mask) / jnp.sum(mask)
    return ce + extra


def _whole(cfg, batches):
    """Losses of steps 1-3 and the clipped first gradient's leaf norms,
    with AdamW written out over the whole tree."""
    specs = model.leaf_specs(cfg)
    p = model.flat_leaves(model.make_weights(cfg, SEED), cfg)
    vg = jax.jit(jax.value_and_grad(lambda flat, b: _whole_loss(
        cfg, model.nest({s[0]: x for s, x in zip(specs, flat)}), b)))
    b1, b2 = OPT["b1"], OPT["b2"]
    losses, grads, clips = [], [], []
    m = v = None
    for t in (1, 2, 3):
        loss, g = vg(p, batches[t - 1])
        losses.append(float(loss))
        if t == 3:
            break
        gn = math.sqrt(sum(float(jnp.sum(x * x)) for x in g))
        c = min(1.0, OPT["grad_clip"] / (gn + 1e-9))
        grads.append(g)
        clips.append(c)
        g = [c * x for x in g]
        m = [(1 - b1) * x if m is None else b1 * a + (1 - b1) * x
             for a, x in zip(m or g, g)]
        v = [(1 - b2) * x * x if v is None else b2 * a + (1 - b2) * x * x
             for a, x in zip(v or g, g)]
        lr = reference.lr_at(t - 1, OPT)
        new = []
        for (_, shape, _, stack), x, mx, vx in zip(specs, p, m, v):
            rank = len(shape) - (stack is not None)
            wd = OPT["weight_decay"] if rank >= 2 else 0.0
            u = (mx / (1 - b1 ** t)) / (jnp.sqrt(vx / (1 - b2 ** t))
                                         + OPT["eps"])
            new.append(x - lr * (u + wd * x))
        p = new
    norms = [clips[0] * float(jnp.sqrt(jnp.sum(x * x))) for x in grads[0]]
    return losses, norms


def _worst_gap(cfg):
    batches = _batches(cfg)
    ref = reference.train_reference(cfg, SEED, batches, OPT)
    losses, norms = _whole(cfg, batches)
    return max(abs(a - b) / abs(b) for a, b in
               zip(ref["losses"] + ref["grad_norms"], losses + norms))


def test_stacks_and_loss_term_match_the_whole_model(cfg):
    stacks = [s for _, _, _, s in model.leaf_specs(cfg)]
    assert model.layer_stacks(cfg) == ["dense"] + ["routed"] * 3
    assert "zeros" in [i for _, _, i, _ in model.leaf_specs(cfg)]
    assert {None, "dense", "routed"} == set(stacks)
    assert _worst_gap(cfg) < TOL


def test_a_dropped_loss_term_is_seen(cfg, monkeypatch):
    real = reference.Reference._layer

    def drop(self, stack, lp, h, pos):
        out = real(self, stack, lp, h, pos)
        return out[0] if isinstance(out, tuple) else out

    monkeypatch.setattr(reference.Reference, "_layer", drop)
    assert _worst_gap(cfg) > 100 * TOL

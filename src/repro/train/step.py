"""train_step / eval_step builders: pure functions ready for jit/pjit.

``make_train_step`` returns ``step(params, opt_state, batch) -> (params,
opt_state, metrics)`` with microbatch gradient accumulation (lax.scan) and
the configured optimizer.  Sharding is injected via the active
ShardingCtx (parallel/context.py) + in/out shardings computed by the
caller (launch/dryrun.py, train/loop.py).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import RunConfig
from repro.models import loss_fn
from repro.optim import (
    adamw_update, clip_by_global_norm, lr_at, init_state,
    init_error, compress_decompress,
)

Params = Any
Batch = Dict[str, jax.Array]


def make_opt_state(run: RunConfig, params: Params) -> Dict[str, Any]:
    state = init_state(params, run.optim)
    if run.optim.grad_compress == "int8":
        state["ef_error"] = init_error(params)
    return state


def _split_microbatches(batch: Batch, n: int) -> Batch:
    """[B, ...] -> [n, B/n, ...] (positions for VLM split on dim 1)."""
    def split(name, x):
        if name == "positions" and x.ndim == 3 and x.shape[0] == 3:
            return jnp.moveaxis(
                x.reshape(3, n, x.shape[1] // n, *x.shape[2:]), 1, 0)
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])

    return {k: split(k, v) for k, v in batch.items()}


def make_train_step(run: RunConfig) -> Callable:
    cfg = run.model
    n_micro = run.microbatches

    def train_step(params: Params, opt_state: Dict[str, Any], batch: Batch,
                   ) -> Tuple[Params, Dict[str, Any], Dict[str, jax.Array]]:
        def lossm(p, b):
            return loss_fn(cfg, p, b)

        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                lossm, has_aux=True)(params, batch)
        else:
            micro = _split_microbatches(batch, n_micro)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = jax.value_and_grad(lossm, has_aux=True)(
                    params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), ms = jax.lax.scan(
                acc_body, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss / n_micro
            metrics = jax.tree.map(lambda x: x[-1], ms)

        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(grads, run.optim.grad_clip)
            new_ef = None
            if run.optim.grad_compress == "int8":
                grads, new_ef = compress_decompress(grads,
                                                    opt_state["ef_error"])
            lr = lr_at(opt_state["count"], run.optim)
            core_state = {k: opt_state[k] for k in ("m", "v", "count")}
            new_params, new_state = adamw_update(grads, core_state, params,
                                                 lr, run.optim)
            if new_ef is not None:
                new_state["ef_error"] = new_ef
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        for k in ("ce", "aux", "z"):
            if k in metrics:
                out_metrics[k] = metrics[k]
        return new_params, new_state, out_metrics

    return train_step


def make_eval_step(run: RunConfig) -> Callable:
    cfg = run.model

    def eval_step(params: Params, batch: Batch) -> Dict[str, jax.Array]:
        loss, metrics = loss_fn(cfg, params, batch)
        return metrics

    return eval_step

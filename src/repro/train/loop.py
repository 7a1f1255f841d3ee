"""Trainer: the end-to-end loop wiring every substrate together.

Per step: data batch (XUFS-cached shards) -> jitted train_step ->
write-behind checkpoint pump (the WAL drains toward home on the virtual
WAN while compute proceeds) -> callback pump (invalidations) -> fault
monitor protocol (heartbeats / stragglers / restarts).

Crash recovery = exactly the paper's story: restart, ``client.sync()``
replays the meta-op queue, restore from the newest *complete* manifest.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint.ckpt import CheckpointManager
from repro.config.base import RunConfig
from repro.data.pipeline import DataPipeline
from repro.models import init_params
from repro.train.fault import FaultMonitor
from repro.train.step import make_train_step, make_opt_state


@dataclass
class TrainResult:
    steps_run: int
    restarts: int
    final_loss: float
    losses: List[float] = field(default_factory=list)
    checkpoints: List[int] = field(default_factory=list)


class Trainer:
    def __init__(self, run: RunConfig, pipeline: DataPipeline,
                 ckpt: CheckpointManager, *,
                 monitor: Optional[FaultMonitor] = None,
                 ckpt_every: int = 10, pump_ops_per_step: int = 2):
        self.run = run
        self.pipeline = pipeline
        self.ckpt = ckpt
        self.monitor = monitor or FaultMonitor(n_workers=1)
        self.ckpt_every = ckpt_every
        self.pump_ops_per_step = pump_ops_per_step
        # the step consumes the state it replaces: one live copy on device
        self.step_fn = jax.jit(make_train_step(run), donate_argnums=(0, 1))
        self.params: Any = None
        self.opt_state: Any = None
        self.step = 0

    # ---- state ------------------------------------------------------------
    def _init_state(self) -> Dict[str, Any]:
        params = init_params(self.run.model, jax.random.PRNGKey(self.run.seed))
        return {"params": params, "opt": make_opt_state(self.run, params)}

    def initialize(self) -> None:
        state = jax.jit(self._init_state)()
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = 0

    def _state_tree(self) -> Dict[str, Any]:
        return {"params": self.params, "opt": self.opt_state}

    def save_checkpoint(self) -> None:
        self.ckpt.save(self.step, self._state_tree(),
                       extra={"data": self.pipeline.state()})

    def restore_latest(self) -> bool:
        """Post-crash: replay the WAL, then restore the newest manifest.

        The live state is dropped first and the restore is shaped by a
        template of shapes, so the device never holds two copies."""
        self.ckpt.client.sync()
        self.params = self.opt_state = None
        try:
            tree, manifest = self.ckpt.restore(
                jax.eval_shape(self._init_state))
        except FileNotFoundError:
            return False
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = int(manifest["step"])
        if "data" in manifest.get("extra", {}):
            self.pipeline.restore(manifest["extra"]["data"])
        return True

    # ---- loop ------------------------------------------------------------
    def train(self, num_steps: int) -> TrainResult:
        if self.params is None:
            self.initialize()
        losses: List[float] = []
        saved: List[int] = []
        target = self.step + num_steps
        while self.step < target:
            participating, must_restart = self.monitor.begin_step(self.step)
            if must_restart:
                # node failure: elastic re-mesh + restore from checkpoint
                self.monitor.replace_dead()
                restored = self.restore_latest()
                if not restored:
                    self.initialize()
                continue
            batch = self.pipeline.next_batch()
            with obs.span("train.dispatch"):
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch)
            with obs.span("train.loss_sync"):
                losses.append(float(metrics["loss"]))
            self.step += 1
            # write-behind: drain a few WAL ops toward home per step
            self.ckpt.client.pump(max_ops=self.pump_ops_per_step)
            self.ckpt.client.pump_callbacks()
            if self.step % self.ckpt_every == 0:
                self.save_checkpoint()
                saved.append(self.step)
        return TrainResult(steps_run=num_steps,
                           restarts=self.monitor.restarts,
                           final_loss=losses[-1] if losses else float("nan"),
                           losses=losses, checkpoints=saved)

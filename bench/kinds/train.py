"""Training cells: ``Trainer.train`` over a ``DataPipeline`` that reads
shards through a two-site fabric, with checkpoints saved through it.

Set-up builds one ``Trainer`` with weights made from the seed, drives its
first three steps through the window's own call (``train(1)``) and keeps
what the check compares: each step's loss, the first gradient as the
optimiser holds it after step 1, and the weights' change after step 2.
The window then calls ``train(1)`` until ``--seconds`` have passed; a
step under way at the deadline finishes and counts, and the window ends
with it.  The check compares the readings with the plain reference, and,
where the window saved a checkpoint, reads the last one back through a
second client after ``client.sync()``.
"""
from __future__ import annotations

import contextlib
import gc
import math
import os
import statistics
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus, flops, model, reference, trace
from bench.common import BenchError, Spans

SPANS = ("step", "input", "ckpt_save", "wal_pump")
NEVER = 2 ** 62     # ckpt_every for a cell that saves no checkpoint


class TimedPipeline:
    """The program's pipeline, with a span around each ``next_batch``."""

    def __init__(self, pipe, spans: Spans):
        self.pipe, self.spans = pipe, spans

    def next_batch(self):
        with self.spans.span("input"):
            return self.pipe.next_batch()

    def state(self):
        return self.pipe.state()

    def restore(self, state):
        self.pipe.restore(state)


class TimedClient:
    """The fabric client, with a span around each WAL pump."""

    def __init__(self, client, spans: Spans):
        self._client, self._spans = client, spans

    def pump(self, *a, **kw):
        with self._spans.span("wal_pump"):
            return self._client.pump(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._client, name)


class TimedCheckpoints:
    """The checkpoint manager, with a span around each save.  Before each
    save it keeps a checksum of every leaf saved, for the read-back."""

    def __init__(self, mgr, spans: Spans):
        self.mgr, self.spans = mgr, spans
        self.client = TimedClient(mgr.client, spans)
        self.saved: Dict[int, List[int]] = {}

    def save(self, step, tree, *, extra=None):
        self.saved[step] = checksums(tree)
        with self.spans.span("ckpt_save"):
            return self.mgr.save(step, tree, extra=extra)

    def restore(self, *a, **kw):
        return self.mgr.restore(*a, **kw)


@jax.jit
def _checksums(leaves):
    def one(x):
        x = x.reshape(-1)
        bits = jax.lax.bitcast_convert_type(
            x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
        w = jax.lax.iota(jnp.uint32, x.shape[0]) % 65521 + 1
        return jnp.sum(bits.astype(jnp.uint32) * w, dtype=jnp.uint32)
    return jnp.stack([one(x) for x in leaves])


def checksums(tree) -> List[int]:
    """Position-weighted sum of each leaf's raw bits, mod 2**32 (exact)."""
    return [int(c) for c in np.asarray(_checksums(jax.tree.leaves(tree)))]


def _int8_dequant(leaf, block: int):
    """The program's blockwise-int8 moment ``{"q", "s"}`` as float32."""
    q, s = leaf["q"], leaf["s"]
    D = q.shape[-1]
    nb = s.shape[-1]
    qp = jnp.pad(q.astype(jnp.float32),
                 [(0, 0)] * (q.ndim - 1) + [(0, nb * block - D)])
    x = qp.reshape(*q.shape[:-1], nb, block) * s[..., None]
    return x.reshape(*q.shape[:-1], nb * block)[..., :D]


class Cell:
    def __init__(self, name: str, cfg: Dict[str, Any], mix: Dict[str, Any],
                 seed: int, spans: Spans, workdir: str, chips: int):
        self.name, self.cfg, self.mix, self.seed = name, cfg, mix, seed
        self.spans, self.workdir, self.chips = spans, workdir, chips
        self.readings: Dict[str, Any] = {}

    # ---- set-up ------------------------------------------------------------
    def setup(self) -> None:
        from repro.checkpoint import CheckpointManager
        from repro.config import OptimConfig, RunConfig, ShapeConfig
        from repro.core import Fabric, FabricSpec, MountSpec, SiteSpec
        from repro.data.pipeline import DataPipeline
        from repro.models import init_params
        from repro.train import Trainer
        from repro.train.step import make_opt_state

        mix, cfg = self.mix, self.cfg
        B, S = mix["batch"], mix["seq"]
        self.pcfg = model.program_config(cfg)
        self.fabric = Fabric(FabricSpec(sites=(
            SiteSpec("home", root=os.path.join(self.workdir, "home")),
            SiteSpec("site", root=os.path.join(self.workdir, "site")))))
        self.session = self.fabric.login(
            "trainer", mounts=[MountSpec("home/", ("home/scratch/",))])
        c = mix["corpus"]
        with self.spans.span("setup:corpus"):
            corpus.write_shards(self.session.client, "home/data", self.seed,
                                c["shards"], c["shard_tokens"],
                                cfg["vocab_size"])
        pipe = DataPipeline(self.session.client, "home/data", self.pcfg,
                            batch=B, seq=S, seed=self.seed,
                            n_shards=c["shards"])
        o = mix["optim"]
        self.run = RunConfig(
            model=self.pcfg, shape=ShapeConfig(self.name, "train", S, B),
            optim=OptimConfig(
                lr=o["lr"], warmup_steps=o["warmup_steps"],
                total_steps=o["total_steps"], weight_decay=o["weight_decay"],
                b1=o["b1"], b2=o["b2"], eps=o["eps"],
                grad_clip=o["grad_clip"], state_dtype=o["state_dtype"],
                int8_block=o["int8_block"]),
            seed=self.seed % 2 ** 31)
        self.ckpt = TimedCheckpoints(
            CheckpointManager(self.session.client, "home/ckpt"), self.spans)
        self.trainer = Trainer(self.run, TimedPipeline(pipe, self.spans),
                               self.ckpt,
                               ckpt_every=mix["ckpt_every"] or NEVER)

        with self.spans.span("setup:weights"):
            params = jax.block_until_ready(model.make_weights(cfg, self.seed))
        want = jax.eval_shape(lambda: init_params(self.pcfg,
                                                  jax.random.PRNGKey(0)))
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise BenchError("the program's weights differ in layout from "
                             f"the benchmark's for {cfg['name']}")
        self.trainer.params = params
        self.trainer.opt_state = jax.jit(
            lambda p: make_opt_state(self.run, p))(params)

        # the first three steps, through the window's own call
        with self.spans.span("setup:step1"):
            losses = self.trainer.train(1).losses
        with self.spans.span("setup:readings"):
            self.readings["grad_norms"] = self._first_gradient_norms()
        with self.spans.span("setup:steps2-3"):
            losses += self.trainer.train(1).losses
            (self.readings["delta_norms"],
             self.readings["delta_sketches"]) = self._change()
            losses += self.trainer.train(1).losses
        self.readings["losses"] = losses
        if self.ckpt.saved:
            raise BenchError("a checkpoint fell in set-up; raise ckpt_every")
        if mix["ckpt_every"]:
            with self.spans.span("setup:checksums"):
                checksums(self.trainer._state_tree())  # compiles the check

    def _first_gradient_norms(self) -> List[float]:
        """Leaf norms of the clipped gradient of step 1, from the first
        moment the optimiser keeps: m = (1 - b1) * g after one update."""
        o, m = self.mix["optim"], self.trainer.opt_state["m"]
        leaves = model.flat_leaves(m, self.cfg)
        if o["state_dtype"] == "int8":
            leaves = [_int8_dequant(x, o["int8_block"]) for x in leaves]
        norms = jax.jit(lambda ls: jnp.stack(
            [jnp.sqrt(jnp.sum(jnp.square(x))) for x in ls]))(leaves)
        return [float(n) / (1 - o["b1"]) for n in np.asarray(norms)]

    def _change(self):
        """Leaf norms and sketches (:func:`bench.model.sketch`) of
        (weights now - the seed's weights)."""
        cfg, key = self.cfg, _root(self.seed)
        stacked = [stack is not None for *_, stack in model.leaf_specs(cfg)]

        def f(params, key):
            norms, sketches = [], []
            for i, p in enumerate(model.flat_leaves(params, cfg)):
                p0 = model.make_leaf(cfg, key, i)
                d = p.astype(jnp.float32) - p0.astype(jnp.float32)
                norms.append(jnp.sqrt(jnp.sum(d * d)))
                if stacked[i]:
                    sk = jax.vmap(model.sketch)(
                        d, jnp.arange(d.shape[0], dtype=jnp.uint32)).sum(0)
                else:
                    sk = model.sketch(d)
                sketches.append(sk)
            return jnp.stack(norms), jnp.stack(sketches)

        norms, sketches = jax.jit(f)(self.trainer.params, key)
        return ([float(x) for x in np.asarray(norms)],
                np.asarray(sketches))

    # ---- the timed window ----------------------------------------------------
    def window(self, seconds: float, traced: bool) -> Dict[str, Any]:
        B, S = self.mix["batch"], self.mix["seq"]
        losses: List[float] = []
        ev: Dict[str, Any] = {}
        ctx = trace.record(ev) if traced else contextlib.nullcontext()
        with ctx:
            with self.spans.span("window"):
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < seconds:
                    with self.spans.span("step"):
                        losses += self.trainer.train(1).losses
                t1 = time.perf_counter()
        rec: Dict[str, Any] = {
            "window_s": t1 - t0, "steps": len(losses),
            "tokens": len(losses) * B * S,
            "attempted": len(losses),
            "failed": sum(1 for x in losses if not math.isfinite(x)),
            "step_flops": flops.train_step(self.cfg, B, S),
            "input_s": self.spans.durations("input", t0, t1),
            "ckpt_save_s": self.spans.durations("ckpt_save", t0, t1),
            "chips": self.chips,
        }
        if traced:
            lo, hi = trace.window_bounds(ev["events"], "window")
            rec["trace"] = trace.summarize(ev["events"], lo, hi, SPANS,
                                           step_match="train_step")
        return rec

    # ---- the check -------------------------------------------------------------
    def check(self, controls: bool = False) -> Dict[str, Any]:
        """Numbers compared with the reference (and, with ``controls``,
        the readings of the fp8 control and the half-rows fault)."""
        self.trainer.params = self.trainer.opt_state = None
        gc.collect()
        out: Dict[str, Any] = {}
        if self.mix["ckpt_every"]:
            out["ckpt_readback_errors"] = self._read_back()
        mix, cfg = self.mix, self.cfg
        c = mix["corpus"]
        bs = [tuple(jnp.asarray(a) for a in corpus.batch(
            self.seed, j, mix["batch"], mix["seq"], c["shards"],
            c["shard_tokens"], cfg["vocab_size"])) + (
            jnp.ones((mix["batch"], mix["seq"]), jnp.float32),)
            for j in range(3)]
        ref = reference.train_reference(cfg, self.seed, bs, mix["optim"])
        out.update(gaps(self.readings, ref))
        if controls:
            for tag, kw in (("fp8", dict(precision="fp8")),
                            ("half_rows", dict(rows="half"))):
                alt = reference.train_reference(cfg, self.seed, bs,
                                                mix["optim"], **kw)
                out.update({f"{k}.{tag}": v for k, v in
                            gaps(alt, ref).items()})
        return out

    def _read_back(self) -> int:
        """Errors in reading the last checkpoint back from home through a
        second client after ``sync()``: leaves whose bits differ, a LATEST
        that names another step, or no checkpoint at all."""
        from repro.checkpoint import CheckpointManager
        from repro.core import MountSpec

        if not self.ckpt.saved:
            return 1
        last = max(self.ckpt.saved)
        with self.spans.span("ckpt_sync"):
            self.session.client.sync()
        reader = self.fabric.attach(self.session, "site", owner="reader",
                                    mounts=[MountSpec("home/")])
        mgr = CheckpointManager(reader, "home/ckpt")
        template = jax.eval_shape(self.trainer._init_state)
        with self.spans.span("ckpt_restore"):
            tree, manifest = mgr.restore(template)
        got = checksums(tree)
        jax.tree.map(lambda x: x.delete(), tree)
        want = self.ckpt.saved[last]
        errors = int(manifest["step"] != last) + int(len(got) != len(want))
        return errors + sum(1 for a, b in zip(got, want) if a != b)


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    """The numbers compared: the largest relative loss gap of steps 1-2;
    by the worst leaf the gap of the first gradient's norm and of the
    weights' change (``update_gap``), each over the larger of that leaf's
    reference norm and the median leaf's; and, over the same denominator,
    the norm of the difference of the two changes' sketches
    (``change_gap``), which sees a change that points the wrong way.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of the change.  Step 3's loss gap (``loss3_gap``),
    the only loss after an update, is reported beside them."""
    rel = [abs(a - b) / abs(b) for a, b in
           zip(prog["losses"], ref["losses"])]
    g_med = statistics.median(ref["grad_norms"])
    grad = max(abs(a - b) / max(b, g_med) for a, b in
               zip(prog["grad_norms"], ref["grad_norms"]))
    d_med = statistics.median(ref["delta_norms"])
    moved = [g >= 1e-3 * g_med for g in ref["grad_norms"]]
    upd = max(abs(a - b) / max(b, d_med) for a, b, m in
              zip(prog["delta_norms"], ref["delta_norms"], moved) if m)
    chg = max(float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
              / max(n, d_med) for a, b, n, m in
              zip(prog["delta_sketches"], ref["delta_sketches"],
                  ref["delta_norms"], moved) if m)
    return {"loss_gap": max(rel[:2]), "loss3_gap": rel[2],
            "grad_gap": grad, "update_gap": upd, "change_gap": chg}


def _root(seed):
    from bench.common import root_key
    return root_key(seed)

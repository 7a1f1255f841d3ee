"""Smoke run of the ML path on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: serve + train phases
    python chip_smoke.py --chips 4   # four chips: sharded training only

One chip, one process, two phases:

* serve — ``qwen3-4b`` at its published widths and depth (bf16 weights from
  a seed).  The weights are published with ``CheckpointManager.save`` and
  ``client.sync()`` on a two-site ``Fabric``, the device copy is dropped,
  and they come back through ``restore`` against a template of shapes, so
  the chip never holds two copies.  A ``ServeEngine`` then serves 8 requests
  on 4 slots.  Checks: restored checksums equal the published ones, every
  token is in ``[0, vocab)``, and the last decode tick of one request
  matches a plain ``forward`` over its prompt and output.
* train — ``Trainer`` over a ``DataPipeline`` at ``qwen3-4b`` widths (bf16
  params, int8 moments, seq 4096) for 5 steps with one checkpoint save,
  then ``restore_latest()``.  Depth is cut to what the compiled step fits.

``--chips 4`` runs the fsdp train step of ``qwen3-8b`` widths on a 2x2
``(data, model)`` mesh of the four chips and checks ``loss_fn`` sharded
against one device.

Earlier lines are one JSON object per phase; the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase fails a
check or raises, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import CheckpointManager  # noqa: E402
from repro.config import (  # noqa: E402
    OptimConfig, RunConfig, ShapeConfig, ShardingConfig,
)
from repro.configs import get_config  # noqa: E402
from repro.core import Fabric, FabricSpec, MountSpec, SiteSpec  # noqa: E402
from repro.data.batches import make_batch, make_specs  # noqa: E402
from repro.data.pipeline import DataPipeline, SyntheticCorpus  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.models import forward, init_params, loss_fn, param_axes  # noqa: E402
from repro.optim import state_axes  # noqa: E402
from repro.parallel.context import sharding_ctx  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    batch_shardings, make_ctx, sanitize_shardings, tree_shardings,
)
from repro.serve.engine import Request, ServeEngine  # noqa: E402
from repro.train import Trainer  # noqa: E402
from repro.train.step import make_opt_state, make_train_step  # noqa: E402

SEED = 0

# serve: 8 requests, prompt lengths spread over 16..512 tokens
PROMPT_LENS = (16, 40, 88, 136, 200, 288, 400, 512)
SLOTS, MAX_NEW, MAX_LEN = 4, 32, 1024
# The last decode tick against a plain forward: both run bf16 activations
# (f32 softmax) in a different order, so they differ by bf16 roundings that
# accumulate over the depth.  Bound: the relative RMS error of the logit
# row stays within 16 bf16 ulps (16 * 2**-8).
LOGIT_RTOL = 16 * 2.0 ** -8

# train: the train_4k shape, one sequence per chip (train_4k's 256
# sequences over a 256-chip pod).  Depth: the step compiled for a described
# v5e peaks at 15.37 GiB of 15.75 GiB with 9 layers and is refused with 10.
TRAIN_SEQ, TRAIN_BATCH, TRAIN_LAYERS = 4096, 1, 9
TRAIN_STEPS, CKPT_EVERY = 5, 3

# four chips: qwen3-8b's fsdp step on a 2x2 mesh, one sequence per chip.
# Depth: compiled for a described v5e:2x2 the step peaks at 14.90 GiB per
# chip with 14 layers; 15 and 16 are refused.  The sharded-vs-one-device
# loss runs at a depth one chip holds, within the bound of
# tests/test_sharding_multidev.py.
SHARDED_SEQ, SHARDED_BATCH, SHARDED_LAYERS, SHARDED_STEPS = 4096, 4, 14, 3
CMP_SEQ, CMP_BATCH, CMP_LAYERS, CMP_ATOL = 2048, 2, 2, 2e-2


def _device_label():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(record):
    print(json.dumps(record), flush=True)


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _two_sites(workdir):
    return Fabric(FabricSpec(sites=(
        SiteSpec("home", root=os.path.join(workdir, "home")),
        SiteSpec("site", root=os.path.join(workdir, "site")),
    )))


@jax.jit
def _leaf_checksum(x):
    bits = jax.lax.bitcast_convert_type(
        x.reshape(-1), {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
    w = jax.lax.iota(jnp.uint32, bits.shape[0]) % 65521 + 1
    return jnp.sum(bits.astype(jnp.uint32) * w, dtype=jnp.uint32)


def checksums(tree):
    """Per-leaf position-weighted sum of the raw bits, mod 2**32 (exact)."""
    return [int(_leaf_checksum(x)) for x in jax.tree.leaves(tree)]


def serve_phase(cfg, workdir):
    rec = {"phase": "serve", "device": _device_label(), "model": cfg.name,
           "layers": cfg.num_layers, "d_model": cfg.d_model,
           "params": cfg.param_count()}
    s = _two_sites(workdir).login("server")
    init = jax.jit(lambda: init_params(cfg, jax.random.PRNGKey(SEED)))

    t0 = time.perf_counter()
    params = init()
    published = checksums(params)
    rec["init_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    mgr = CheckpointManager(s.client, f"home/models/{cfg.name}")
    mgr.save(0, {"params": params})
    s.client.sync()
    rec["publish_s"] = time.perf_counter() - t0
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params

    t0 = time.perf_counter()
    restored, _ = mgr.restore({"params": jax.eval_shape(init)})
    params = jax.block_until_ready(restored["params"])
    rec["restore_s"] = time.perf_counter() - t0
    _check(checksums(params) == published,
           "restored weights differ from the published ones")

    engine = ServeEngine(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                         seed=SEED)
    rng = np.random.default_rng(SEED)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .tolist(), max_new_tokens=MAX_NEW)
            for i, n in enumerate(PROMPT_LENS)]
    for r in reqs:
        engine.add_request(r)

    # keep the logits of the watched request's decode ticks (the engine
    # only hands back tokens)
    watched = reqs[-1]
    last = {}
    decode = engine._decode

    def recording_decode(p, t, c):
        logits, c = decode(p, t, c)
        for i, st in enumerate(engine.slot_states):
            if st.active and st.rid == watched.rid:
                last["logits"] = logits[i, 0]
        return logits, c

    engine._decode = recording_decode
    t0 = time.perf_counter()
    ticks = 0
    while engine.queue or any(st.active for st in engine.slot_states):
        engine.step()
        ticks += 1
    rec["serve_s"] = time.perf_counter() - t0
    rec.update(requests=len(reqs), ticks=ticks,
               tokens=sum(len(r.output) for r in reqs))

    for r in reqs:
        _check(r.done and len(r.output) == MAX_NEW,
               f"request {r.rid} emitted {len(r.output)} tokens")
        _check(all(0 <= t < cfg.vocab_size for t in r.output),
               f"request {r.rid} emitted a token outside the vocabulary")

    seq = watched.prompt + watched.output[:-1]
    S = len(seq)
    ref_logits, _ = jax.jit(lambda p, b: forward(cfg, p, b))(params, {
        "tokens": jnp.asarray([seq], jnp.int32),
        "positions": jnp.arange(S, dtype=jnp.int32)[None]})
    ref = np.asarray(ref_logits[0, -1], np.float32)
    got = np.asarray(last["logits"], np.float32)
    rel = float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))
    rec.update(logit_rel_rms=rel, logit_rel_bound=LOGIT_RTOL,
               logit_max_abs_diff=float(np.max(np.abs(got - ref))),
               argmax_agrees=bool(np.argmax(got) == np.argmax(ref)))
    _check(np.all(np.isfinite(got)), "non-finite decode logits")
    _check(rel <= LOGIT_RTOL,
           f"decode logits off the plain forward: rel rms {rel:.4g}")
    rec["peak_bytes_in_use"] = _peak_bytes()
    _emit(rec)


def train_phase(cfg, workdir, *, seq, batch, reduced):
    rec = {"phase": "train", "device": _device_label(), "model": cfg.name,
           "layers": cfg.num_layers, "d_model": cfg.d_model,
           "params": cfg.param_count(), "seq": seq, "batch": batch,
           "reduced": reduced}
    s = _two_sites(workdir).login(
        "trainer", mounts=[MountSpec("home/", ("home/scratch/",))])
    SyntheticCorpus(s.client, "home/data", seed=SEED, vocab=cfg.vocab_size,
                    shard_tokens=max(batch * seq * 2, 8192)).materialize(4)
    pipe = DataPipeline(s.client, "home/data", cfg, batch=batch, seq=seq,
                        seed=SEED, n_shards=4)
    run = RunConfig(model=cfg, shape=ShapeConfig("train_4k", "train", seq,
                                                 batch),
                    optim=OptimConfig(state_dtype="int8"), seed=SEED)
    trainer = Trainer(run, pipe, CheckpointManager(s.client, "home/ckpt"),
                      ckpt_every=CKPT_EVERY)

    t0 = time.perf_counter()
    first = trainer.train(1)
    rec["first_step_s"] = time.perf_counter() - t0   # init + compile
    t0 = time.perf_counter()
    rest = trainer.train(TRAIN_STEPS - 1)
    rec["next_steps_s"] = time.perf_counter() - t0   # one save included
    losses = first.losses + rest.losses
    rec.update(steps=len(losses), losses=losses, saved=rest.checkpoints,
               tokens=len(losses) * batch * seq)
    _check(len(losses) == TRAIN_STEPS and np.all(np.isfinite(losses)),
           f"losses {losses}")
    _check(rest.checkpoints == [CKPT_EVERY],
           f"checkpoints saved at {rest.checkpoints}")

    t0 = time.perf_counter()
    _check(trainer.restore_latest(), "no checkpoint to restore")
    rec["restore_s"] = time.perf_counter() - t0
    _check(trainer.step == CKPT_EVERY,
           f"restored step {trainer.step}, saved {CKPT_EVERY}")
    rec["restored_step"] = trainer.step
    rec["peak_bytes_in_use"] = _peak_bytes()
    _emit(rec)


def sharded_programs(cfg, mesh, *, seq, batch):
    """The fsdp train step and its state initializer, placed on ``mesh``.

    Parameters and optimizer state are created under their shardings, so
    no chip ever holds the whole state.  Returns (ctx, init, step,
    batch_shardings)."""

    run = RunConfig(model=cfg, shape=ShapeConfig("train_4k", "train", seq,
                                                 batch),
                    optim=OptimConfig(state_dtype="int8"),
                    sharding=ShardingConfig(policy="fsdp"), seed=SEED)
    ctx = make_ctx(mesh, run.sharding)

    def init_state():
        p = init_params(cfg, jax.random.PRNGKey(SEED))
        return p, make_opt_state(run, p)

    p_spec, o_spec = jax.eval_shape(init_state)
    p_sh = sanitize_shardings(tree_shardings(ctx, param_axes(cfg)), p_spec)
    o_sh = sanitize_shardings(
        tree_shardings(ctx, state_axes(param_axes(cfg), run.optim)), o_spec)
    b_sh = batch_shardings(ctx, make_specs(cfg, batch, seq))
    init = jax.jit(init_state, out_shardings=(p_sh, o_sh))
    step = jax.jit(make_train_step(run), in_shardings=(p_sh, o_sh, b_sh),
                   out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))
    return ctx, init, step, b_sh


def sharded_phase(cfg, *, reduced):
    rec = {"phase": "sharded_train", "device": _device_label(),
           "model": cfg.name, "layers": cfg.num_layers,
           "d_model": cfg.d_model, "params": cfg.param_count(),
           "mesh": {"data": 2, "model": 2}, "seq": SHARDED_SEQ,
           "batch": SHARDED_BATCH, "reduced": reduced}
    mesh = make_test_mesh(2, 2)
    ctx, init, step, b_sh = sharded_programs(cfg, mesh, seq=SHARDED_SEQ,
                                             batch=SHARDED_BATCH)
    losses = []
    t0 = time.perf_counter()
    with sharding_ctx(ctx):
        p, o = init()
        for i in range(SHARDED_STEPS):
            b = jax.device_put(make_batch(cfg, SHARDED_BATCH, SHARDED_SEQ,
                                          jax.random.PRNGKey(i)), b_sh)
            p, o, m = step(p, o, b)
            losses.append(float(m["loss"]))
            if i == 0:
                rec["first_step_s"] = time.perf_counter() - t0
    rec["next_steps_s"] = time.perf_counter() - t0 - rec["first_step_s"]
    rec["losses"] = losses
    _check(np.all(np.isfinite(losses)), f"losses {losses}")
    del p, o

    # loss_fn at a depth one chip holds: sharded vs one device
    small = cfg.replace(num_layers=CMP_LAYERS)
    ps = jax.jit(lambda: init_params(small, jax.random.PRNGKey(SEED)))()
    batch = make_batch(small, CMP_BATCH, CMP_SEQ)
    lf = lambda p, b: loss_fn(small, p, b)[0]
    l_one = float(jax.jit(lf)(ps, batch))
    p_sh = sanitize_shardings(tree_shardings(ctx, param_axes(small)), ps)
    cb_sh = batch_shardings(ctx, batch)
    with sharding_ctx(ctx):
        l_sharded = float(jax.jit(lf, in_shardings=(p_sh, cb_sh))(
            jax.device_put(ps, p_sh), jax.device_put(batch, cb_sh)))
    diff = abs(l_one - l_sharded)
    rec.update(cmp_layers=CMP_LAYERS, cmp_seq=CMP_SEQ, cmp_batch=CMP_BATCH,
               loss_one_device=l_one, loss_sharded=l_sharded,
               loss_abs_diff=diff, loss_bound=CMP_ATOL,
               peak_bytes_in_use=_peak_bytes())
    _check(diff < CMP_ATOL, f"sharded loss {l_sharded} vs one device {l_one}")
    _emit(rec)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    cache_dir = use_compile_cache()

    dev = _device_label()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev['platform']}); nothing "
              "was run", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {dev['count']} "
              "found", file=sys.stderr)
        return 2
    print(f"# compile cache: {cache_dir}", flush=True)

    if args.chips == 4:
        cfg = get_config("qwen3-8b").replace(param_dtype="bfloat16",
                                             num_layers=SHARDED_LAYERS)
        sharded_phase(cfg, reduced={
            "num_layers": f"36 -> {SHARDED_LAYERS}",
            "global_batch": f"256 -> {SHARDED_BATCH}"})
    else:
        cfg = get_config("qwen3-4b").replace(param_dtype="bfloat16")
        with tempfile.TemporaryDirectory(prefix="smoke_serve_") as wd:
            serve_phase(cfg, wd)
        gc.collect()
        _check(not jax.live_arrays(), "serve phase left arrays on device")
        with tempfile.TemporaryDirectory(prefix="smoke_train_") as wd:
            train_phase(cfg.replace(num_layers=TRAIN_LAYERS), wd,
                        seq=TRAIN_SEQ, batch=TRAIN_BATCH, reduced={
                            "num_layers": f"36 -> {TRAIN_LAYERS}",
                            "global_batch": f"256 -> {TRAIN_BATCH}"})

    print(json.dumps({"ok": True, "device": _device_label()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Spans, counters and named scopes (``repro.obs``): what a profiler trace
of the program holds, and that the fabric stays free of JAX."""
import glob
import os
import re
import subprocess
import sys

import jax
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.config import OptimConfig, RunConfig, ShapeConfig
from repro.configs import get_tiny_config
from repro.core import Fabric, FabricSpec, MountSpec
from repro.data.batches import batch_shapes
from repro.data.pipeline import DataPipeline, SyntheticCorpus
from repro.models import init_params
from repro.train import Trainer
from repro.train.step import make_opt_state, make_train_step

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
HOST = "/host:CPU"


def _trace(tmp_path, body):
    """Run ``body`` under a CPU profiler trace; the host plane's events as
    ``(line, name, start_ns, end_ns)``."""
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    return [(line.name, e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == HOST for line in plane.lines
            for e in line.events]


def test_span_without_jax_is_a_no_op_and_imports_nothing():
    code = ("import sys\n"
            "from repro import obs\n"
            "import repro.core\n"
            "with obs.span('wal.append'):\n"
            "    with obs.span('wal.flush'):\n"
            "        pass\n"
            "assert obs.span('a') is obs.span('b')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr


def test_count_adds():
    before = obs.counts.get("test.count", 0)
    obs.count("test.count")
    obs.count("test.count", 41)
    assert obs.counts["test.count"] - before == 42


def test_spans_land_on_the_host_plane_of_a_cpu_trace(tmp_path):
    def body():
        with obs.span("test.outer"):
            with obs.span("test.inner"):
                pass
    ev = {name: (line, s, e) for line, name, s, e in _trace(tmp_path, body)}
    assert "test.outer" in ev and "test.inner" in ev
    (lo, so, eo), (li, si, ei) = ev["test.outer"], ev["test.inner"]
    assert lo == li and so <= si <= ei <= eo


PER_STEP = ("pipeline.read", "pipeline.to_device", "train.dispatch",
            "train.loss_sync", "wal.flush")
PER_SAVE = ("ckpt.save", "ckpt.pull", "ckpt.encode", "xufs.write",
            "xufs.close", "xufs.cache_store", "wal.append")


def _inside(inner, outer):
    """Each ``inner`` span nested in some ``outer`` span of its thread."""
    return all(any(lo == li and so <= si and ei <= eo
                   for lo, so, eo in outer) for li, si, ei in inner)


def test_a_trainer_run_with_one_save_yields_every_span_nested(tmp_path):
    s = Fabric(FabricSpec.star(str(tmp_path / "h"), str(tmp_path / "s"))) \
        .login("sci", mounts=[MountSpec("home/", ("home/scratch/",))])
    cfg = get_tiny_config("qwen3-4b")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 2),
                    optim=OptimConfig(lr=1e-3, warmup_steps=1,
                                      total_steps=10, state_dtype="int8"))
    SyntheticCorpus(s.client, "home/data", seed=0, vocab=cfg.vocab_size,
                    shard_tokens=4096).materialize(2)
    pipe = DataPipeline(s.client, "home/data", cfg, batch=2, seq=32,
                        n_shards=2)
    tr = Trainer(run, pipe, CheckpointManager(s.client, "home/ckpt"),
                 ckpt_every=3)
    tr.train(1)                                   # compiles outside the trace
    before = obs.counts.get("ckpt.bytes", 0)
    events = _trace(tmp_path, lambda: tr.train(3))  # steps 2-4, saves at 3
    by = {}
    for line, name, st, en in events:
        by.setdefault(name, []).append((line, st, en))
    for name in PER_STEP:
        assert len(by.get(name, [])) == 3, name
    for name in PER_SAVE:
        assert by.get(name), name
    assert len(by["ckpt.save"]) == 1
    n_leaves = len(jax.tree.leaves(tr._state_tree()))
    assert len(by["ckpt.pull"]) == len(by["ckpt.encode"]) == n_leaves
    assert obs.counts["ckpt.bytes"] - before == sum(
        x.nbytes for x in jax.tree.leaves(tr._state_tree()))
    save = by["ckpt.save"]
    for name in ("ckpt.pull", "ckpt.encode", "xufs.write", "xufs.close"):
        assert _inside(by[name], save), name
    for name in ("wal.append", "xufs.cache_store"):
        assert _inside(by[name], by["xufs.close"]), name
    assert not _inside(by["wal.flush"], save)


def _segments(op_name):
    """The scopes of an ``op_name``, with JAX's transform wrappers such as
    ``transpose(jvp(head))`` seen through."""
    out = []
    for seg in op_name.split("/"):
        while True:
            m = re.fullmatch(r"[\w.-]+\((.*)\)", seg)
            if not m:
                break
            seg = m.group(1)
        out.append(seg)
    return out


@pytest.mark.parametrize("state_dtype", ["int8", "float32"])
def test_the_train_step_names_its_four_scopes(state_dtype):
    cfg = get_tiny_config("qwen3-4b")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 32, 2),
                    optim=OptimConfig(state_dtype=state_dtype))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda p: make_opt_state(run, p), params)
    batch = {k: jax.ShapeDtypeStruct(shape, dt)
             for k, (shape, dt) in batch_shapes(cfg, 2, 32).items()}
    step = jax.jit(make_train_step(run))
    assert step.__name__ == "train_step"     # the device program's name
    hlo = step.lower(params, opt, batch).as_text(dialect="hlo",
                                                 debug_info=True)
    scopes = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        scopes.update(_segments(name))
    assert {"attention", "mlp", "head", "optimizer"} <= scopes

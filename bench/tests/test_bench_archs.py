"""The lookup of a configuration's architecture module by ``model_type``."""
from __future__ import annotations

import json
import os

import jax
import pytest

from bench import model, run
from bench.common import BenchError
from bench.kinds import train
from bench.tests import tiny


def _configs():
    out = []
    for c in tiny.spec()["configs"]:
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            out.append(json.load(f))
    return out


def test_unknown_model_type_fails_before_setup(tmp_path, monkeypatch):
    def setup(self):
        raise AssertionError("set-up ran")

    monkeypatch.setattr(train.Cell, "setup", setup)
    with open(os.path.join(tiny.ROOT, "bench", "configs",
                           "qwen3-4b-l1.json")) as f:
        cfg = json.load(f)
    cfg.update(name="odd", model_type="no_such_arch")
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "odd.json").write_text(json.dumps(cfg))
    spec = tiny.spec()
    spec["workloads"] = [{"name": "odd.mix", "config": "odd",
                          "traffic": "train-4k", "chips": 1, "why": "test"}]
    with pytest.raises(BenchError, match="bench/archs/no_such_arch.py"):
        run.measure("odd.mix", tiny.SEED, 0.5, False, spec=spec,
                    need_tpu=False, data_dir=str(tmp_path))


@pytest.mark.parametrize("model_type", [None, "../qwen3", "qwen3.x"])
def test_model_type_that_names_no_module_is_refused(model_type):
    with pytest.raises(BenchError, match="model_type"):
        model.arch({"name": "odd", "model_type": model_type})


@pytest.mark.parametrize("cfg", _configs(), ids=lambda c: c["name"])
def test_every_configuration_resolves_and_passes_its_check(cfg):
    a = model.arch(cfg)
    assert os.path.basename(a.__file__) == f"{cfg['model_type']}.py"
    assert model.check_config(dict(cfg)) == cfg


@pytest.mark.parametrize("cfg", _configs(), ids=lambda c: c["name"])
def test_leaf_specs_match_the_programs_parameters(cfg):
    from bench.common import import_program

    import_program()
    from repro.models import init_params

    cfg = dict(cfg, **model.arch(cfg).TINY)
    want = jax.eval_shape(lambda: init_params(model.program_config(cfg),
                                              jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: model.make_weights(cfg, tiny.SEED))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # every stacked leaf has one slice per layer of its stack
    n = dict(model.arch(cfg).stacks(cfg))
    for path, shape, _, stack in model.leaf_specs(cfg):
        assert stack is None or shape[0] == n[stack], path

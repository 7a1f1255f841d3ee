"""The harness without a chip: a cell made only of data files is found by
name, and a run that finds no TPU fails without a result line."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from bench import run
from bench.tests import tiny


def test_cell_of_data_files_only_is_found_by_name(tmp_path):
    """A new configuration, traffic mix and limits, written as files, and a
    new entry in the spec: the harness runs the new cell unchanged."""
    src = os.path.join(tiny.ROOT, "bench")
    for d in ("configs", "traffic", "limits"):
        (tmp_path / d).mkdir()
    cfg = json.load(open(os.path.join(src, "configs", "qwen3-4b-l1.json")))
    cfg.update(tiny.CONFIG, name="tiny-new")
    json.dump(cfg, open(tmp_path / "configs" / "tiny-new.json", "w"))
    mix = json.load(open(os.path.join(src, "traffic", "train-4k.json")))
    mix.update(tiny.TRAFFIC["train"], seq=64)
    json.dump(mix, open(tmp_path / "traffic" / "new-mix.json", "w"))
    shutil.copy(os.path.join(src, "limits", "qwen3-4b.train-4k.json"),
                tmp_path / "limits" / "tiny-new.new-mix.json")
    spec = tiny.spec()
    spec["workloads"] = [{"name": "tiny-new.new-mix", "config": "tiny-new",
                          "traffic": "new-mix", "chips": 1, "why": "test"}]
    spec["end_to_end"] = [dict(m, workloads=["tiny-new.new-mix"])
                          for m in spec["end_to_end"]
                          if m["name"] in ("setup_s", "train_tokens_per_s")]
    out = run.measure("tiny-new.new-mix", tiny.SEED, 0.5, False, spec=spec,
                      need_tpu=False, data_dir=str(tmp_path))
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "train_tokens_per_s"}


def test_metrics_follow_the_spec():
    spec = tiny.spec()
    for w in spec["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(spec, w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = run.metrics_for(spec, w["name"], True)
        assert layer and all(m["moves"] in e2e for m in layer)
        for m in layer + run.metrics_for(spec, w["name"], False):
            assert os.path.exists(os.path.join(
                tiny.ROOT, "bench", "metrics", m["name"] + ".py"))


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.ROOT, "bench", "run.py"),
         "--workload", tiny.spec()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

"""Training cells rehearsed end to end on the CPU at a tiny size, and the
faults the check must catch (a step that returns its state unchanged, a
loss over half of the rows)."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests import tiny


def _run(workload, **kw):
    return run.measure(workload, tiny.SEED, 1.0, False, need_tpu=False,
                       overrides=tiny.overrides(workload), spec=tiny.spec(),
                       **kw)


@pytest.mark.parametrize("workload", tiny.workloads("train"))
def test_train_cell_runs_and_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    e2e = {m["name"] for m in run.metrics_for(tiny.spec(), workload, False)}
    assert set(out["metrics"]) == e2e and len(e2e) >= 2
    assert all(m["value"] > 0 for m in out["metrics"].values())
    for c in out["checks"].values():
        assert c["value"] is not None


def test_state_left_unchanged_is_not_correct(monkeypatch):
    import repro.train.loop as loop

    real = loop.make_train_step

    def broken(run_cfg):
        step = real(run_cfg)

        def same_state(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return same_state

    monkeypatch.setattr(loop, "make_train_step", broken)
    out = _run(tiny.workloads("train")[0])
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] > 0.9


def test_half_the_rows_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    import repro.train.step as step

    real = step.loss_fn

    def half(cfg, params, batch):
        t = batch["targets"]
        keep = jnp.arange(t.shape[1]) < t.shape[1] // 2
        return real(cfg, params, dict(batch, targets=jnp.where(
            keep[None, :], t, -1)))

    monkeypatch.setattr(step, "loss_fn", half)
    out = _run(tiny.workloads("train")[0])
    assert not out["correct"]


def test_update_with_the_wrong_sign_is_not_correct(monkeypatch):
    import jax
    import repro.train.loop as loop

    real = loop.make_train_step

    def broken(run_cfg):
        step = real(run_cfg)

        def backwards(params, opt, batch):
            new, opt2, metrics = step(params, opt, batch)
            flipped = jax.tree.map(
                lambda p, q: (2 * p.astype("float32")
                              - q.astype("float32")).astype(p.dtype),
                params, new)
            return flipped, opt2, metrics
        return backwards

    monkeypatch.setattr(loop, "make_train_step", broken)
    out = _run(tiny.workloads("train")[0])
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] > 1.0

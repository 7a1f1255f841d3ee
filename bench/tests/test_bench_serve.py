"""The serving cell rehearsed end to end on the CPU at a tiny size, and a
served token altered where it is produced, which the check must catch."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests import tiny


def _run(workload):
    return run.measure(workload, tiny.SEED, 4.0, False, need_tpu=False,
                       overrides=tiny.overrides(workload), spec=tiny.spec())


@pytest.mark.parametrize("workload", tiny.workloads("serve"))
def test_serve_cell_runs_and_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    m = out["metrics"]
    assert m["ttft_p90_ms"]["value"] > 0 and "itl_p95_ms" in m


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine.step

    def altered(self):
        n = real(self)
        for st in self.slot_states:
            if st.active:
                req = self.requests[st.rid]
                req.output[-1] = (req.output[-1] + 1) % self.cfg.vocab_size
                break
        return n

    monkeypatch.setattr(ServeEngine, "step", altered)
    out = _run(tiny.workloads("serve")[0])
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]

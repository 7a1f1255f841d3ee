"""The control: the plain reference put in the program's place, one
precision below the configuration's (float8 for bfloat16), must come out
not correct under each cell's limits.  Run here at a size a test run
holds; the readings at the cells' own sizes are in PERF.md."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests import tiny


# At the tiny widths a random model's best logit stands far above the
# rest, and float8 still picks it; at these widths its gaps are as the
# served model's are (PERF.md), so the serving control is read here.
SERVE_CONFIG = dict(tiny.CONFIG, hidden_size=1024, num_attention_heads=8,
                    num_key_value_heads=2, head_dim=128,
                    intermediate_size=2048, vocab_size=32768)
# At the tiny widths float8 moves a step's loss and its update less than it
# does at the cells' own sizes (PERF.md), so the training control is read
# at these widths, where it separates from the program as on the chip.
TRAIN_CONFIG = dict(tiny.CONFIG, hidden_size=256, head_dim=64,
                    intermediate_size=512, vocab_size=4096)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      tiny.spec()["workloads"]])
def test_control_is_not_correct(workload):
    o = tiny.overrides(workload)
    seconds = 1.0
    if tiny.traffic(workload)["kind"] == "serve":
        o["config"] = SERVE_CONFIG
        o["traffic"].update(requests=16, check_tokens=60)
        seconds = 4.0
    else:
        o["config"] = TRAIN_CONFIG
    out = run.measure(workload, tiny.SEED, seconds, False, need_tpu=False,
                      overrides=o, controls=True, spec=tiny.spec())
    limits = {k: c["limit"] for k, c in out["checks"].items()}
    control = {k[:-len(".fp8")]: v for k, v in out["_info"].items()
               if k.endswith(".fp8")}
    assert control, "the check read no control"
    # judged on the numbers the control reads (not the checkpoint read-back
    # or the sample's size, which it has no reading of)
    lim = {k: v for k, v in limits.items() if k in control}
    program = {k: out["checks"][k]["value"] for k in lim}
    assert run.judge(program, lim), (program, lim)
    assert not run.judge(control, lim), (control, lim)

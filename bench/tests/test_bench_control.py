"""The control: the plain reference put in the program's place, one
precision below the configuration's (float8 for bfloat16), must come out
not correct under each cell's limits.  Run here at a size a test run
holds; the readings at the cells' own sizes are in PERF.md."""
from __future__ import annotations

import pytest

from bench import run
from bench.tests import tiny


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      tiny.spec()["workloads"]])
def test_control_is_not_correct(workload):
    o = tiny.overrides(workload)
    kind = tiny.traffic(workload)["kind"]
    # the widths at which the control separates as at the cell's own size
    o["config"] = dict(tiny.arch(workload).CONTROL[kind])
    seconds = 1.0
    if kind == "serve":
        o["traffic"].update(requests=16, check_tokens=60)
        seconds = 4.0
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

"""The trace reduction on small traces whose busy, idle and collective
times are known."""
from __future__ import annotations

import pytest

from bench import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


def _hand_made():
    """Window 0..100 ns.  Device ops: 10-30 (fusion), 20-40 (a collective
    overlapping it), 50-60 (all-reduce alone), 70-90 (fusion).  Host spans:
    "input" 40-55 and "step" 0-100 (the outer one)."""
    return [
        (DEV, OPS, "fusion.1", 10.0, 30.0),
        (DEV, OPS, "all-gather-start.2", 20.0, 40.0),
        (DEV, OPS, "all-reduce.3", 50.0, 60.0),
        (DEV, OPS, "fusion.4", 70.0, 90.0),
        (DEV, MODS, "jit_train_step", 10.0, 60.0),
        (DEV, MODS, "jit_train_step", 70.0, 90.0),
        (HOST, "python", "step", 0.0, 100.0),
        (HOST, "python", "input", 40.0, 55.0),
        (HOST, "python", "window", 0.0, 100.0),
    ]


def test_busy_idle_and_collectives_of_a_hand_made_trace():
    ev = _hand_made()
    s = trace.summarize(ev, 0.0, 100.0, ("step", "input"),
                        step_match="train_step")
    # busy: 10-40, 50-60, 70-90 = 60 ns
    assert s["busy_s"] == pytest.approx(60e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert trace.idle_percent(s) == pytest.approx(40.0)
    # exposed collective time: 30-40 and 50-60 = 20 ns
    assert s["collective_exposed_s"] == pytest.approx(20e-9)
    assert s["step_s"] == pytest.approx([50e-9, 20e-9])
    # idle 0-10, 40-50, 60-70, 90-100: "input" covers 40-50 (innermost)
    gaps = dict(s["idle_gaps"])
    assert gaps["input"] == pytest.approx(10e-9)
    assert gaps["step"] == pytest.approx(30e-9)
    assert dict(s["device_ops"])["fusion.1"] == pytest.approx(20e-9)


def test_clipped_to_the_window():
    ev = _hand_made()
    assert trace.busy_seconds(ev, 25.0, 75.0) == pytest.approx(30e-9)


def test_a_trace_recorded_on_a_tpu():
    """``data/trace_tpu.json``, recorded by ``record_trace.py`` on a TPU v5e:
    three runs of a program of two 2048-square bf16 matmuls, each in a host
    span ``step``, with a 50 ms sleep in a span ``input`` after each."""
    import json
    import os

    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_tpu.json")) as f:
        rec = json.load(f)
    assert ["/device:TPU:0", trace.OPS_LINE] in rec["planes_and_lines"]
    ev = [tuple(e) for e in rec["events"]]
    lo, hi = trace.window_bounds(ev, "window")
    s = trace.summarize(ev, lo, hi, ("step", "input"),
                        step_match="train_step")
    assert len(s["step_s"]) == 3
    assert all(1.7e-4 < t < 1.9e-4 for t in s["step_s"])
    # the device's clock leads the host's by some microseconds, so the
    # window clips the first run's start
    assert 0.9 * sum(s["step_s"]) < s["busy_s"] <= sum(s["step_s"])
    gaps = dict(s["idle_gaps"])
    assert gaps["input"] == pytest.approx(0.15, abs=0.005)
    assert 99.0 < trace.idle_percent(s) < 100.0
    ops = dict(s["device_ops"])
    assert sum(ops.values()) == pytest.approx(sum(s["step_s"]), rel=0.01)
    assert s["collective_exposed_s"] == 0.0

"""The open-loop schedule: every seed gets the same sizes and gaps in
another order, at the mix's mean rate, within the prompt and output laws."""
from __future__ import annotations

import json
import os

from bench import arrivals
from bench.tests import tiny


def _mix():
    with open(os.path.join(tiny.ROOT, "bench", "traffic",
                           "serve-poisson.json")) as f:
        return json.load(f)


def test_same_work_for_every_seed():
    mix = _mix()
    a = arrivals.schedule(mix, 1, 1000)
    b = arrivals.schedule(mix, tiny.SEED, 1000)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    # the whole schedule arrives over requests / rate seconds
    last = mix["requests"] / mix["rate_per_s"]
    assert a[-1]["due_s"] <= last and b[-1]["due_s"] <= last


def test_lengths_within_the_laws():
    mix = _mix()
    allowed = set(arrivals.prompt_lengths(mix))
    for r in arrivals.schedule(mix, 7, 1000):
        assert len(r["prompt"]) in allowed
        assert mix["output"]["min"] <= r["max_new"] <= mix["output"]["max"]
        assert all(0 <= t < 1000 for t in r["prompt"])
    assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]


def test_sample_holds_the_longest_and_enough_tokens():
    class R:
        def __init__(self, p, o):
            self.prompt, self.output = [0] * p, [0] * o
    done = [R(64, 16), R(768, 256), R(128, 40), R(64, 30), R(256, 20)]
    s = arrivals.sample(done, 300, 5)
    assert s[0] is done[1]
    assert sum(len(r.output) for r in s) >= 300
    assert arrivals.sample([], 10, 1) == []


def test_seeds_above_32_bits_are_accepted():
    mix = _mix()
    assert arrivals.schedule(mix, 2 ** 40 + 3, 1000)

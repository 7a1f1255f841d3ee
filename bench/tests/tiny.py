"""Tiny sizes for rehearsing cells on the CPU: every width cut (by the
``TINY`` overrides of the configuration's architecture module), the
traffic shortened, so that a whole run takes seconds."""
from __future__ import annotations

import json
import os

from bench import model
from bench.archs import qwen3

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Qwen3's overrides, for the tests that shrink a Qwen3 file by hand.
CONFIG = qwen3.TINY

TRAFFIC = {
    "train": {"seq": 128, "corpus": {"shards": 4, "shard_tokens": 4096}},
    "serve": {"slots": 4, "max_len": 128, "requests": 8, "rate_per_s": 8.0,
              "prompt": {"median": 32, "sigma": 0.8, "min": 16, "max": 64,
                         "step": 16},
              "output": {"median": 8, "sigma": 0.8, "min": 4, "max": 16},
              "trace_from_s": 0.2, "trace_seconds": 0.5,
              "check_tokens": 20, "drain_s": 30},
}

SEED = 2 ** 31 + 12345     # larger than 32 signed bits, as the driver's are


def spec():
    """``BENCHMARK.json`` with the cells held out of it until they are
    measured on the chip (``data/held_cells.json``), so that the tests
    rehearse those too."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        out = json.load(f)
    with open(os.path.join(ROOT, "bench", "tests", "data",
                           "held_cells.json")) as f:
        held = json.load(f)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {x["name"] for x in out[key]}
        out[key] += [x for x in held[key] if x["name"] not in have]
    return out


def traffic(workload: str):
    w = next(w for w in spec()["workloads"] if w["name"] == workload)
    with open(os.path.join(ROOT, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        return json.load(f)


def config(workload: str):
    w = next(w for w in spec()["workloads"] if w["name"] == workload)
    with open(os.path.join(ROOT, "bench", "configs",
                           f"{w['config']}.json")) as f:
        return json.load(f)


def arch(workload: str):
    """The architecture module of a cell's configuration."""
    return model.arch(config(workload))


def overrides(workload: str):
    mix = traffic(workload)
    t = dict(TRAFFIC[mix["kind"]])
    if mix.get("ckpt_every"):
        t["ckpt_every"] = 6        # saves fall in a short window
    return {"config": dict(arch(workload).TINY), "traffic": t}


def workloads(kind: str):
    return [w["name"] for w in spec()["workloads"]
            if traffic(w["name"])["kind"] == kind]

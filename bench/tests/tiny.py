"""Tiny sizes for rehearsing cells on the CPU: every width cut, the
traffic shortened, so that a whole run takes seconds."""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CONFIG = {"hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
          "vocab_size": 512, "num_hidden_layers": 2}

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


def overrides(workload: str):
    mix = traffic(workload)
    t = dict(TRAFFIC[mix["kind"]])
    if mix.get("ckpt_every"):
        t["ckpt_every"] = 6        # saves fall in a short window
    return {"config": CONFIG, "traffic": t}


def workloads(kind: str):
    return [w["name"] for w in spec()["workloads"]
            if traffic(w["name"])["kind"] == kind]

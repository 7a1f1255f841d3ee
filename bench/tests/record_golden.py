"""Record the Qwen3 golden numbers on the CPU, for ``test_bench_golden.py``.

    JAX_PLATFORMS=cpu python bench/tests/record_golden.py \
        > bench/tests/data/qwen3_golden.json

For ``tiny.CONFIG`` at ``tiny.SEED``: the leaf checksums of the weights
made from the seed, the plain reference's three losses, first-gradient
leaf norms, leaf norms of the change and norms of its sketches over a
``train-4k`` step at the tiny traffic's length, and the operation counts
of a train step, a prefill and a decode token at the tiny and at the
published widths.  The test recomputes :func:`numbers` and asks for the
same values, bit for bit.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.tests import tiny  # noqa: E402

CONFIG_FILE = "qwen3-4b-l11"
TRAFFIC_FILE = "train-4k"


def _load(*parts):
    with open(os.path.join(tiny.ROOT, "bench", *parts)) as f:
        return json.load(f)


def numbers():
    import jax.numpy as jnp
    import numpy as np

    from bench import corpus, flops, model, reference
    from bench.kinds import train

    full = _load("configs", f"{CONFIG_FILE}.json")
    cfg = dict(full, **tiny.CONFIG)
    mix = _load("traffic", f"{TRAFFIC_FILE}.json")
    mix.update(tiny.TRAFFIC["train"])
    c, B, S = mix["corpus"], mix["batch"], mix["seq"]
    bs = [tuple(jnp.asarray(a) for a in corpus.batch(
        tiny.SEED, j, B, S, c["shards"], c["shard_tokens"],
        cfg["vocab_size"])) + (jnp.ones((B, S), jnp.float32),)
        for j in range(3)]
    ref = reference.train_reference(cfg, tiny.SEED, bs, mix["optim"])
    counts = {}
    for tag, conf, seq in (("tiny", cfg, S), ("published", full, 4096)):
        counts[tag] = {
            "train_step": flops.train_step(conf, B, seq),
            "prefill": flops.prefill(conf, seq // 2),
            "decode_token": flops.decode_token(conf, seq - 1),
        }
    return {
        "checksums": train.checksums(model.make_weights(cfg, tiny.SEED)),
        "losses": [float(x) for x in ref["losses"]],
        "grad_norms": [float(x) for x in ref["grad_norms"]],
        "delta_norms": [float(x) for x in ref["delta_norms"]],
        "sketch_norms": [float(np.linalg.norm(np.asarray(s, np.float64)))
                         for s in ref["delta_sketches"]],
        "flops": counts,
    }


def main() -> int:
    print(json.dumps(numbers(), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

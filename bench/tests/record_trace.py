"""Record a small profiler trace on the chip, for the trace reduction's
test (``test_bench_trace.py``).

    python bench/tests/record_trace.py > bench/tests/data/trace_tpu.json

Inside a host span ``window``: a jitted program of two matmuls run three
times, each call in a host span ``step``, with a host sleep of 50 ms in a
span ``input`` between calls.  Prints the events of the device's op and
program lines and of the host spans, as :func:`bench.trace.load_events`
gives them, with the device's kind, as one JSON object.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace  # noqa: E402
from bench.common import Spans  # noqa: E402

SPAN_NAMES = ("window", "step", "input")


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def train_step(a, b):
        return jnp.tanh(a @ b) @ b

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 1e-3
    train_step(a, b).block_until_ready()
    spans = Spans(annotate=True)
    ev = {}
    with trace.record(ev):
        with spans.span("window"):
            for _ in range(3):
                with spans.span("step"):
                    train_step(a, b).block_until_ready()
                with spans.span("input"):
                    time.sleep(0.05)
    keep = [e for e in ev["events"]
            if (e[0].startswith(trace.DEVICE_PREFIX)
                and e[1] in (trace.OPS_LINE, trace.MODULES_LINE))
            or (e[0] == trace.HOST_PLANE and e[2] in SPAN_NAMES)]
    planes = sorted({(e[0], e[1]) for e in ev["events"]})
    print(json.dumps({"device_kind": jax.devices()[0].device_kind,
                      "planes_and_lines": planes, "events": keep}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

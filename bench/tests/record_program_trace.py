"""Record a small profiler trace of named scopes and a program span on the
chip, for ``test_bench_program_spans.py``.

    python bench/tests/record_program_trace.py \\
        > bench/tests/data/trace_tpu_program.json

Inside a host span ``window``, twice: a jitted ``train_step`` whose first
matmul is under ``jax.named_scope("attention")`` and whose second runs in
a two-step ``lax.scan`` under ``jax.named_scope("mlp")``, then its result
pulled to the host in the program's span ``ckpt.pull`` (``repro.obs``).
Prints the device's op and program lines and those host spans, with the
name stack of each op, as :func:`bench.program_spans.load` gives them.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import program_spans, trace  # noqa: E402
from bench.common import import_program  # noqa: E402

SPAN_NAMES = ("window", "ckpt.pull")


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: no TPU", file=sys.stderr)
        return 2
    import_program()
    from repro import obs

    @jax.jit
    def train_step(a, b):
        with jax.named_scope("attention"):
            y = jnp.tanh(a @ b)

        def body(c, _):
            with jax.named_scope("mlp"):
                return jnp.tanh(c @ b), None

        y, _ = jax.lax.scan(body, y, None, length=2)
        return y

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16) * 1e-3
    train_step(a, b).block_until_ready()
    ev = {}
    with program_spans.record(ev):
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(2):
                y = train_step(a, b)
                with obs.span("ckpt.pull"):
                    np.asarray(y)
    keep = [e for e in ev["events"]
            if (e[0].startswith(trace.DEVICE_PREFIX)
                and e[1] in (trace.OPS_LINE, trace.MODULES_LINE))
            or (e[0] == trace.HOST_PLANE and e[2] in SPAN_NAMES)]
    names = {e[2] for e in keep}
    print(json.dumps({"device_kind": jax.devices()[0].device_kind,
                      "stack_stat": program_spans.STACK_STAT,
                      "events": keep,
                      "stacks": {k: v for k, v in ev["stacks"].items()
                                 if k in names}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

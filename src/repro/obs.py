"""Host spans and counters at the program's layer boundaries.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: while a profiler
trace is recorded it lands in that trace beside the device ops, on the
same clock; otherwise it costs one check.  Where JAX has not been
imported no trace can be running, so ``span`` hands back a shared no-op
and the fabric (``repro.core``) stays free of JAX.  There is no flag and
no exporter: the profiler's trace is the record.

``count(name, n)`` adds ``n`` to ``counts[name]``; a reader takes the
difference over the window it measures.

Names are ``layer.what`` (``ckpt.pull``, ``wal.append``, ...).
"""
from __future__ import annotations

import contextlib
import sys
from typing import ContextManager, Dict

counts: Dict[str, int] = {}

_NOOP = contextlib.nullcontext()


def span(name: str) -> ContextManager:
    jax = sys.modules.get("jax")
    if jax is None:
        return _NOOP
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: int = 1) -> None:
    counts[name] = counts.get(name, 0) + n

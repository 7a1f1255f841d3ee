"""Clock, spans, percentiles, seeds and device facts shared by the harness.

Nothing here imports the program under test.  JAX is imported lazily so
that ``run.py`` can stamp the process start before the first heavy import.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, so that every run after the first finds its programs there.
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent cache at :data:`CACHE_DIR`, caching every
    program however quickly it compiled (the serve path's eager prefill
    compiles many small programs).  Call before the first compilation."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def import_program() -> None:
    """Make the program under test (``src/repro``) importable."""
    src = os.path.join(CHECKOUT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def root_key(seed: int):
    """A PRNG key that uses every bit of a seed of up to 64 bits."""
    import jax

    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def device_facts() -> Dict[str, object]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def quantile(values: Sequence[float], q: float) -> float:
    """The ``q``-quantile (0 < q < 1) by linear interpolation between
    order statistics (numpy's default rule), exact on small samples."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no values")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass
class Spans:
    """Host spans around calls into the program, on the host clock.

    Each span is also a ``jax.profiler.TraceAnnotation``, so that in a
    traced run it lands in the profiler's trace beside the device ops."""

    records: List[Tuple[str, float, float]] = field(default_factory=list)
    annotate: bool = False

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str, since: float = float("-inf"),
                  until: float = float("inf")) -> List[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= since and e <= until]


class BenchError(RuntimeError):
    """The run cannot be measured; the harness prints no result line."""

"""Production mesh construction.

A function (NOT a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches see the default single device.

Every axis is ``AxisType.Auto``: the model code places arrays with
``with_sharding_constraint`` and leaves the rest to the partitioner, which
``jax.make_mesh``'s default of Explicit axes refuses (e.g. the embedding
gather raises ``ShardingTypeError``).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def _make_mesh(shape: Sequence[int], axes: Sequence[str]):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).

    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the leading "pod"
    axis carries only data parallelism (params are pod-cached, XUFS-style),
    so its collectives are the slow-link-friendly gradient reductions.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pods: int = 0):
    """Small mesh over the local devices: four chips of one host, or
    host-platform devices in tests."""
    if pods:
        return _make_mesh((pods, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))

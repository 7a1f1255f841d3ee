"""Model configurations as the benchmark runs them, and their weights.

A configuration file (``configs/<name>.json``) holds the published
``config.json`` keys of its source, with the keys this benchmark changed
listed under ``reduced``.  Its ``model_type`` names the architecture
module ``archs/<model_type>.py``, which holds everything the benchmark
knows of that architecture; this module dispatches to it, and makes
weights from a seed in the tree layout the program takes (each layer
stack's leaves stacked on a leading axis), so that the program and the
plain reference read the same numbers and neither makes them.

An architecture module is plain functions and constants:

- ``check(cfg)`` raises where a key holds a value its reference does not
  implement;
- ``program_config(cfg)``: the program's ``ModelConfig``;
- ``stacks(cfg)``: the layer stacks as ``(name, n_layers)``, in the order
  the layers run;
- ``leaf_specs(cfg)``: ``(path, shape, init, stack)`` of every weight, in
  a fixed order, whose index is the leaf's ``fold_in`` index (so the order
  fixes the weights).  ``init`` is ``normal`` (N(0, initializer_range)),
  ``ones`` or ``zeros``; ``stack`` names the stack of a stacked leaf
  (leading axis one per layer) or is ``None`` for a top-level leaf.  The
  top level holds ``embedding``, ``final_norm`` and, untied, ``unembed``
  as the last key of their paths; a layer holds its stack's leaves under
  their paths less the first key;
- ``layer(cfg, stack, dot, lp, h, pos)``: one layer of the reference in
  float32 through ``dot`` (:data:`bench.reference.DOTS`), returning the
  new hidden state, or it and a scalar the loss adds (a router's balance
  term, say);
- ``matmul_params(cfg, stack)``: matrix parameters a token meets in one
  layer of the stack (of experts, the share this chip computes);
  ``attn_ops(cfg, stack)``: operations per causal query-key pair of such
  a layer; ``head_params(cfg)``: the output head's (:mod:`bench.flops`);
- ``TINY``: key overrides that shrink the architecture for the CPU
  rehearsals, and ``CONTROL``: by the kind of cell, the overrides at
  which its control test reads the control.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
from typing import Any, Dict, List, Tuple

from bench.common import BENCH_DIR, BenchError, root_key


def arch(cfg: Dict[str, Any]):
    """The architecture module of a configuration, by its ``model_type``."""
    mt = cfg.get("model_type")
    if not isinstance(mt, str) or not re.fullmatch(r"[A-Za-z0-9_]+", mt):
        raise BenchError(f"config {cfg.get('name')}: model_type {mt!r} "
                         "does not name an architecture module")
    name = f"bench.archs.{mt}"
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(BENCH_DIR, "archs", f"{mt}.py")
    if not os.path.exists(path):
        raise BenchError(f"config {cfg.get('name')}: no architecture "
                         f"module for model_type {mt!r}; add "
                         f"bench/archs/{mt}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


def check_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    arch(cfg).check(cfg)
    return cfg


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    return arch(cfg).program_config(cfg)


Spec = Tuple[Tuple[str, ...], Tuple[int, ...], str, Any]


def leaf_specs(cfg: Dict[str, Any]) -> List[Spec]:
    """(path, shape, init, stack) of every weight, in a fixed order."""
    return arch(cfg).leaf_specs(cfg)


def layer_stacks(cfg: Dict[str, Any]) -> List[str]:
    """The stack of each layer, in the order the layers run."""
    return [s for s, n in arch(cfg).stacks(cfg) for _ in range(n)]


def make_leaf(cfg: Dict[str, Any], key, index: int):
    """Weight number ``index`` of :func:`leaf_specs`, from the run's root
    key alone (:func:`bench.common.root_key` of the seed)."""
    import jax
    import jax.numpy as jnp

    _, shape, init, _ = leaf_specs(cfg)[index]
    dt = jnp.dtype(cfg["torch_dtype"])
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "zeros":
        return jnp.zeros(shape, dt)
    key = jax.random.fold_in(key, index)
    return (jax.random.normal(key, shape, jnp.float32)
            * cfg["initializer_range"]).astype(dt)


def nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    tree: Dict[str, Any] = {}
    for path, leaf in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return tree


def make_weights(cfg: Dict[str, Any], seed: int):
    """Every weight, made on the device in one jitted call."""
    import jax

    specs = leaf_specs(cfg)

    def build(key):
        return nest({p: make_leaf(cfg, key, i)
                     for i, (p, _, _, _) in enumerate(specs)})

    return jax.jit(build)(root_key(seed))


#: Buckets of a change's sketch (:func:`sketch`).
SKETCH = 1024


def sketch(x, layer=0):
    """A signed count-sketch of one layer's slice of a weight (a top-level
    weight is layer 0), in float32 [SKETCH].

    Element ``j`` of the flattened slice goes to bucket ``j mod SKETCH``
    with the sign of a hash of ``(layer, j)``.  The sketch is linear, and
    its norm estimates the norm of what it sketches (to a few per cent at
    1024 buckets), so the norm of the difference of two changes' sketches
    measures how far they point apart, not only how large each is.  A sum
    over the layers of a stacked weight sketches the whole weight."""
    import jax
    import jax.numpy as jnp

    f = x.reshape(-1).astype(jnp.float32)
    n = f.shape[0]
    f = jnp.pad(f, (0, (-n) % SKETCH))
    h = jax.lax.iota(jnp.uint32, f.shape[0]) + (
        jnp.asarray(layer, jnp.uint32) * jnp.uint32(0x9E3779B9))
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    sign = 1.0 - 2.0 * (h >> 31).astype(jnp.float32)
    return jnp.sum((f * sign).reshape(-1, SKETCH), axis=0)


def flat_leaves(tree: Dict[str, Any], cfg: Dict[str, Any]) -> List[Any]:
    """The leaves of a weight-shaped tree in :func:`leaf_specs` order."""
    out = []
    for path, *_ in leaf_specs(cfg):
        node = tree
        for k in path:
            node = node[k]
        out.append(node)
    return out

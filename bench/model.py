"""Model configurations as the benchmark runs them, and their weights.

A configuration file (``configs/<name>.json``) holds the published
``config.json`` keys of its source, with the keys this benchmark changed
listed under ``reduced``.  This module turns such a file into the
program's ``ModelConfig`` and makes weights for it from a seed, in the
tree layout the program takes (layers stacked on a leading axis), so that
the program and the plain reference read the same numbers and neither
makes them.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

from bench.common import root_key

#: Published keys that must hold these values: the reference implements
#: only this architecture (Qwen3 dense decoder: GQA with per-head QK-norm,
#: rotary positions, SwiGLU, RMSNorm).
_FIXED = {"hidden_act": "silu", "attention_bias": False,
          "use_sliding_window": False, "rope_scaling": None}


def check_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    for k, v in _FIXED.items():
        if cfg.get(k) != v:
            raise ValueError(f"config {cfg['name']}: {k}={cfg.get(k)!r}, "
                             f"the reference implements only {v!r}")
    return cfg


def program_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.config import DENSE, ModelConfig

    return ModelConfig(
        name=cfg["name"], family=DENSE,
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=cfg["attention_bias"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"])


def leaf_specs(cfg: Dict[str, Any]) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], str]]:
    """(path, shape, init) of every weight, in a fixed order.

    ``init`` is ``normal`` (N(0, initializer_range)) or ``ones`` (norm
    scales), as a freshly initialised Qwen3 has them."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    H, K, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    specs = [
        (("embed", "embedding"), (V, D), "normal"),
        (("final_norm",), (D,), "ones"),
        (("blocks", "ln1"), (L, D), "ones"),
        (("blocks", "attn", "wq"), (L, D, H * Dh), "normal"),
        (("blocks", "attn", "wk"), (L, D, K * Dh), "normal"),
        (("blocks", "attn", "wv"), (L, D, K * Dh), "normal"),
        (("blocks", "attn", "wo"), (L, H * Dh, D), "normal"),
        (("blocks", "attn", "q_norm"), (L, Dh), "ones"),
        (("blocks", "attn", "k_norm"), (L, Dh), "ones"),
        (("blocks", "ln2"), (L, D), "ones"),
        (("blocks", "mlp", "wi_gate"), (L, D, F), "normal"),
        (("blocks", "mlp", "wi_up"), (L, D, F), "normal"),
        (("blocks", "mlp", "wo"), (L, F, D), "normal"),
    ]
    if not cfg["tie_word_embeddings"]:
        specs.insert(1, (("embed", "unembed"), (D, V), "normal"))
    return specs


def leaf_name(path: Tuple[str, ...]) -> str:
    return "/".join(path)


def make_leaf(cfg: Dict[str, Any], key, index: int):
    """Weight number ``index`` of :func:`leaf_specs`, from the run's root
    key alone (:func:`bench.common.root_key` of the seed)."""
    import jax
    import jax.numpy as jnp

    _, shape, init = leaf_specs(cfg)[index]
    dt = jnp.dtype(cfg["torch_dtype"])
    if init == "ones":
        return jnp.ones(shape, dt)
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
                     for i, (p, _, _) in enumerate(specs)})

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
    for path, _, _ in leaf_specs(cfg):
        node = tree
        for k in path:
            node = node[k]
        out.append(node)
    return out

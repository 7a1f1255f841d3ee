"""Striped, write-behind checkpointing over the XUFS fabric.

Save path (runs on the caller's thread: the step waits for it; only the
WAL's drain toward home comes later, as ``client.pump()`` ticks or
``client.sync()``):
  1. every leaf tensor is pulled to the host, serialized and ``close()``d
     through the XufsClient -> one aggregated store op per leaf in the WAL;
  2. a manifest (leaf paths, shapes, dtypes, step) is written AFTER all
     leaves — WAL FIFO order guarantees the manifest reaches home only
     once every leaf it references is durable (**last-close-wins commit**);
  3. the LATEST pointer is written after the manifest.
  A crash at any point replays cleanly: ``client.sync()`` drains the WAL
  in order; a LATEST that made it home always names a complete manifest.

Restore: LATEST -> manifest -> leaves; small leaves ride the parallel
prefetcher, large ones the striped fetch — the paper's Fig.4/Fig.5 split.
"""
from __future__ import annotations

import io
import json
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.namespace import XufsClient

Params = Any


def _leaf_paths(tree: Any) -> List[Tuple[Tuple, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return flat


def _path_str(path: Tuple) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _encode(arr: jax.Array) -> bytes:
    a = np.asarray(arr)
    if a.dtype == jnp.bfloat16:   # numpy can't serialize ml_dtypes natively
        a = a.view(np.uint16)
    buf = io.BytesIO()
    np.save(buf, a, allow_pickle=False)
    return buf.getvalue()


def _decode(data: bytes, dtype: str = "") -> np.ndarray:
    a = np.load(io.BytesIO(data), allow_pickle=False)
    if dtype == "bfloat16":
        a = a.view(jnp.bfloat16)
    return a


class CheckpointManager:
    def __init__(self, client: XufsClient, prefix: str, keep: int = 3):
        self.client = client
        self.prefix = prefix.rstrip("/")
        self.keep = keep

    # ---- save -----------------------------------------------------------
    def save(self, step: int, tree: Params, *,
             extra: Optional[Dict] = None) -> str:
        with obs.span("ckpt.save"):
            return self._save(step, tree, extra)

    def _save(self, step: int, tree: Params, extra: Optional[Dict]) -> str:
        base = f"{self.prefix}/step_{step:08d}"
        manifest: Dict[str, Any] = {"step": step, "leaves": [],
                                    "extra": extra or {}}
        for path, leaf in _leaf_paths(tree):
            name = _path_str(path)
            obj = f"{base}/{name}.npy"
            with obs.span("ckpt.pull"):
                arr = np.asarray(leaf)
            obs.count("ckpt.bytes", arr.nbytes)
            with obs.span("ckpt.encode"):
                data = _encode(arr)
            with self.client.open(obj, "w") as f:
                f.write(data)
                del data      # freed before close() copies the buffer
            manifest["leaves"].append(
                {"name": name, "path": obj, "shape": list(arr.shape),
                 "dtype": str(arr.dtype)})
        with self.client.open(f"{base}/MANIFEST.json", "w") as f:
            f.write(json.dumps(manifest).encode())
        with self.client.open(f"{self.prefix}/LATEST", "w") as f:
            f.write(str(step).encode())
        return base

    # ---- restore ----------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        try:
            with self.client.open(f"{self.prefix}/LATEST") as f:
                return int(f.read().decode())
        except FileNotFoundError:
            return None

    def restore(self, template: Params, step: Optional[int] = None,
                ) -> Tuple[Params, Dict]:
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError("no checkpoint published")
        base = f"{self.prefix}/step_{step:08d}"
        with self.client.open(f"{base}/MANIFEST.json") as f:
            manifest = json.loads(f.read().decode())
        by_name = {l["name"]: l for l in manifest["leaves"]}
        # parallel-prefetch the small leaves (norm scales, biases)
        self.client.chdir(base + "/")

        def load(path, leaf):
            name = _path_str(path)
            rec = by_name[name]
            with self.client.open(rec["path"]) as f:
                arr = _decode(f.read(), rec["dtype"])
            assert list(arr.shape) == rec["shape"], (name, arr.shape)
            return jnp.asarray(arr, dtype=leaf.dtype if hasattr(
                leaf, "dtype") else arr.dtype)

        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = [load(path, leaf) for path, leaf in flat]
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), leaves)
        return tree, manifest

    # ---- gc -----------------------------------------------------------------
    def list_steps(self) -> List[int]:
        steps = set()
        for e in self.client.listdir_cached(self.prefix):
            parts = e.path[len(self.prefix) + 1:].split("/")
            if parts and parts[0].startswith("step_"):
                steps.add(int(parts[0][5:]))
        return sorted(steps)

    def gc(self) -> int:
        steps = self.list_steps()
        doomed = steps[:-self.keep] if len(steps) > self.keep else []
        n = 0
        for s in doomed:
            base = f"{self.prefix}/step_{s:08d}"
            for e in self.client.listdir_cached(base):
                self.client.unlink(e.path)
                n += 1
        return n

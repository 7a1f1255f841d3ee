"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Publishes weights into the home store, restores them at the serving site
through the XUFS fabric (striped fetch + small-tensor prefetch), and runs
a continuous-batching workload of synthetic requests.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax

from repro.configs import ARCH_IDS, get_config, get_tiny_config
from repro.core import Fabric, FabricSpec, SiteSpec
from repro.checkpoint import CheckpointManager
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_params
from repro.serve.engine import ServeEngine, Request


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-8b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--workdir", default="")
    args = ap.parse_args()

    cfg = (get_tiny_config(args.arch) if args.tiny
           else get_config(args.arch)).replace(param_dtype="bfloat16")
    workdir = args.workdir or tempfile.mkdtemp(prefix="xufs_serve_")
    fabric = Fabric(FabricSpec(sites=(
        SiteSpec("home", root=os.path.join(workdir, "home")),
        SiteSpec("site", root=os.path.join(workdir, "site")),
    )))
    net = fabric.network
    s = fabric.login("server")

    init = jax.jit(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    mgr = CheckpointManager(s.client, f"home/models/{cfg.name}")
    mgr.save(0, {"params": init()})     # the published copy is not kept
    s.client.sync()
    clock0 = net.clock
    restored, _ = mgr.restore({"params": jax.eval_shape(init)})
    print(f"weights restored through XUFS in {net.clock - clock0:.2f}s WAN")

    engine = ServeEngine(cfg, restored["params"], slots=args.slots,
                         max_len=args.max_len)
    for i in range(args.requests):
        engine.add_request(Request(
            rid=i, prompt=[1 + (i * 7 + j) % (cfg.vocab_size - 2)
                           for j in range(3 + i % 5)],
            max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    ticks = 0
    while (engine.queue or any(st.active for st in engine.slot_states)):
        engine.step()
        ticks += 1
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"{args.requests} requests, {engine.tokens_generated} tokens, "
          f"{ticks} ticks, {engine.tokens_generated / dt:.1f} tok/s "
          f"({dev.platform}: {dev.device_kind} x{jax.device_count()}, "
          f"compilation included)")


if __name__ == "__main__":
    main()

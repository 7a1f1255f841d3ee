import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# A CPU-only compile tool: neither this process nor its per-cell children
# may take an attached TPU, which belongs to one process at a time.
os.environ["JAX_PLATFORMS"] = "cpu"
# NOTE: the lines above MUST run before any other import (jax locks the
# platform and device count on first init).  Everything below is ordinary
# code.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh (single-pod 16x16 = 256 or
multi-pod 2x16x16 = 512 placeholder devices), constructs ShapeDtypeStruct
stand-ins for params / optimizer state / inputs with their NamedShardings,
lowers the jitted step, compiles it, and records:

  * memory_analysis()  — proof the cell fits per-device HBM;
  * cost_analysis()    — per-device FLOPs / bytes for §Roofline;
  * collective op bytes parsed from the post-SPMD HLO text.

Artifacts land in experiments/artifacts/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both]
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config.base import (
    RunConfig, OptimConfig, ShardingConfig, SHAPES, TRAIN, PREFILL, DECODE,
)
from repro.configs import ARCH_IDS, get_config, get_shape, cells
from repro.data.batches import make_specs
from repro.launch.mesh import make_production_mesh
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.roofline import (
    collective_bytes, roofline_terms, model_flops,
)
from repro.models import (
    init_params, param_axes, init_cache, cache_logical_axes, decode_step,
    prefill,
)
from repro.optim import state_axes
from repro.parallel.context import sharding_ctx
from repro.parallel.sharding import (
    make_ctx, tree_shardings, batch_shardings, sanitize_shardings,
)
from repro.train.step import make_train_step, make_opt_state

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "experiments", "artifacts")

# Large models must serve/train fully sharded; small ones can keep the
# latency-friendly TP-only decode layout.
BIG_ARCHS = {"jamba-1.5-large-398b", "qwen2-vl-72b", "dbrx-132b",
             "qwen2.5-32b", "qwen3-moe-30b-a3b", "phi3-medium-14b"}

# The chip the production meshes stand for (its peaks price the roofline).
TARGET_DEVICE_KIND = "TPU v5 lite"


def _cell_run_config(arch: str, shape_name: str, *, policy: str,
                     micro: int) -> RunConfig:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    optim = OptimConfig()
    if arch == "jamba-1.5-large-398b":
        # 398B params: bf16 weights + blockwise-int8 moments to fit 16 GB
        cfg = cfg.replace(param_dtype="bfloat16")
        optim = OptimConfig(state_dtype="int8")
    if shape.kind in (PREFILL, DECODE):
        cfg = cfg.replace(param_dtype="bfloat16")   # serving runs bf16
    if policy == "auto":
        if shape.kind == TRAIN:
            policy = "fsdp"
        else:
            policy = "fsdp" if arch in BIG_ARCHS else "baseline"
    shard_seq = shape_name == "long_500k"
    return RunConfig(
        model=cfg, shape=shape,
        sharding=ShardingConfig(policy=policy, shard_seq=shard_seq),
        optim=optim, microbatches=micro)


def lower_cell(arch: str, shape_name: str, mesh_kind: str, *,
               policy: str = "auto", micro: Optional[int] = None,
               lps: Optional[int] = None) -> Dict[str, Any]:
    shape = get_shape(shape_name)
    if micro is None:
        micro = 4 if shape.kind == TRAIN else 1
    run = _cell_run_config(arch, shape_name, policy=policy, micro=micro)
    cfg = run.model
    if lps and cfg.num_layers % lps == 0 and cfg.family != "hybrid":
        cfg = cfg.replace(layers_per_step=lps)
        run = run.replace(model=cfg)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    ctx = make_ctx(mesh, run.sharding, decode=(shape.kind == DECODE))

    t0 = time.time()
    params_spec = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    p_axes = param_axes(cfg)
    p_shardings = sanitize_shardings(tree_shardings(ctx, p_axes),
                                     params_spec)

    if shape.kind == TRAIN:
        opt_spec = jax.eval_shape(
            lambda: make_opt_state(run, params_spec))
        o_shardings = tree_shardings(ctx, state_axes(p_axes, run.optim))
        if run.optim.grad_compress == "int8":
            o_shardings["ef_error"] = p_shardings
        o_shardings = sanitize_shardings(o_shardings, opt_spec)
        batch_spec = make_specs(cfg, shape.global_batch, shape.seq_len)
        b_shardings = batch_shardings(ctx, batch_spec)
        step = make_train_step(run)
        with sharding_ctx(ctx):
            jitted = jax.jit(step,
                             in_shardings=(p_shardings, o_shardings,
                                           b_shardings),
                             out_shardings=(p_shardings, o_shardings, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_spec, opt_spec, batch_spec)
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(cfg.param_count(active_only=True), tokens,
                         train=True)
    elif shape.kind == PREFILL:
        batch_spec = make_specs(cfg, shape.global_batch, shape.seq_len)
        batch_spec.pop("targets")
        b_shardings = batch_shardings(ctx, batch_spec)
        max_len = shape.seq_len

        def fn(p, b):
            return prefill(cfg, p, b, max_len=max_len)

        with sharding_ctx(ctx):
            jitted = jax.jit(fn, in_shardings=(p_shardings, b_shardings),
                             out_shardings=None)
            lowered = jitted.lower(params_spec, batch_spec)
        tokens = shape.global_batch * shape.seq_len
        mf = model_flops(cfg.param_count(active_only=True), tokens,
                         train=False)
    else:  # DECODE: one new token against a seq_len-deep cache
        B = shape.global_batch
        cache_spec = jax.eval_shape(
            lambda: init_cache(cfg, B, shape.seq_len))
        c_axes = cache_logical_axes(cfg, shard_seq=run.sharding.shard_seq)
        c_shardings = sanitize_shardings(tree_shardings(ctx, c_axes),
                                         cache_spec)
        tok_spec = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        tok_sharding = ctx.sharding(("batch", None))

        def fn(p, t, c):
            return decode_step(cfg, p, t, c)

        with sharding_ctx(ctx):
            jitted = jax.jit(fn, in_shardings=(p_shardings, tok_sharding,
                                               c_shardings),
                             out_shardings=(None, c_shardings),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_spec, tok_spec, cache_spec)
        tokens = B
        mf = model_flops(cfg.param_count(active_only=True), tokens,
                         train=False)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes(hlo)          # flat (per-occurrence) reference
    loopaware = hlo_analyze(hlo)          # trip-count-aware (the real terms)

    flops_dev = float(loopaware["flops"])
    bytes_dev = float(loopaware["traffic_bytes"])
    coll_dev = float(loopaware["collective_total"])
    terms = roofline_terms(flops_dev, bytes_dev, coll_dev,
                           device_kind=TARGET_DEVICE_KIND)
    mem_fields = {}
    for f in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem_fields[f] = getattr(mem, f, None)

    art = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "devices": int(n_dev), "policy": run.sharding.policy,
        "microbatches": run.microbatches,
        "tokens": tokens,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "loopaware": loopaware,
        "xla_cost_analysis": {k: float(v) for k, v in cost.items()
                              if isinstance(v, (int, float))},
        "collectives_flat": coll,
        "memory": mem_fields,
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops_dev if flops_dev else None,
        "roofline": terms,
    }
    return art


def save_artifact(art: Dict[str, Any], outdir: str) -> str:
    os.makedirs(outdir, exist_ok=True)
    name = f"{art['arch']}__{art['shape']}__{art['mesh']}"
    if art.get("tag"):
        name += f"__{art['tag']}"
    path = os.path.join(outdir, name + ".json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--policy", choices=("auto", "baseline", "fsdp"),
                    default="auto")
    ap.add_argument("--micro", type=int, default=None)
    ap.add_argument("--lps", type=int, default=None,
                    help="layers per scan step (remat grouping)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(ARTIFACTS))
    args = ap.parse_args()

    todo = []
    if args.all:
        for arch in ARCH_IDS:
            for shape_name in cells(arch):
                meshes = (["single", "multi"] if args.mesh == "both"
                          else [args.mesh])
                for mk in meshes:
                    todo.append((arch, shape_name, mk))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        meshes = (["single", "multi"] if args.mesh == "both"
                  else [args.mesh])
        todo = [(args.arch, args.shape, mk) for mk in meshes]

    if args.all:
        # one subprocess per cell: bounds compiler memory, isolates failures
        import subprocess
        failures = 0
        for arch, shape_name, mesh_kind in todo:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", mesh_kind, "--policy", args.policy,
                   "--out", args.out]
            if args.micro is not None:
                cmd += ["--micro", str(args.micro)]
            if args.lps is not None:
                cmd += ["--lps", str(args.lps)]
            if args.tag:
                cmd += ["--tag", args.tag]
            r = subprocess.run(cmd)
            failures += 1 if r.returncode else 0
        print(f"dry-run matrix done: {len(todo) - failures}/{len(todo)} OK",
              flush=True)
        return 1 if failures else 0

    failures = 0
    for arch, shape_name, mesh_kind in todo:
        label = f"{arch} x {shape_name} x {mesh_kind}"
        try:
            art = lower_cell(arch, shape_name, mesh_kind,
                             policy=args.policy, micro=args.micro,
                             lps=args.lps)
            if args.tag:
                art["tag"] = args.tag
            path = save_artifact(art, args.out)
            r = art["roofline"]
            print(f"OK   {label}: dominant={r['dominant']} "
                  f"compute={r['compute_s']*1e3:.1f}ms "
                  f"memory={r['memory_s']*1e3:.1f}ms "
                  f"coll={r['collective_s']*1e3:.1f}ms "
                  f"compile={art['compile_s']:.0f}s -> {path}",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"FAIL {label}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

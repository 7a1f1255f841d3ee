"""End-to-end training driver: a ~100M-param Qwen3-family LM trained for a
few hundred steps through the full stack (XUFS data fabric, write-behind
checkpointing, fault injection mid-run, crash recovery).

    PYTHONPATH=src python examples/train_lm.py --preset full    # ~100M, 300 steps
    PYTHONPATH=src python examples/train_lm.py --preset smoke   # CI-sized

The full preset is sized for a real accelerator; on this CPU-only
container use --preset smoke (identical code path, smaller widths).
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.config import (
    ModelConfig, RunConfig, ShapeConfig, OptimConfig, DENSE,
)
from repro.core import Fabric, FabricSpec, MountSpec, SiteSpec
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import SyntheticCorpus, DataPipeline
from repro.train import Trainer, FaultMonitor, FaultEvent

PRESETS = {
    # ~100M params: 12L x 640d x 10H, vocab 32k
    "full": dict(layers=12, d_model=640, heads=10, kv_heads=5, d_ff=2560,
                 vocab=32768, seq=1024, batch=8, steps=300, micro=2),
    "smoke": dict(layers=2, d_model=128, heads=4, kv_heads=2, d_ff=512,
                  vocab=2048, seq=64, batch=4, steps=30, micro=1),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=PRESETS, default="smoke")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    p = PRESETS[args.preset]
    steps = args.steps or p["steps"]

    cfg = ModelConfig(
        name=f"lm-{args.preset}", family=DENSE, num_layers=p["layers"],
        d_model=p["d_model"], num_heads=p["heads"],
        num_kv_heads=p["kv_heads"], head_dim=p["d_model"] // p["heads"],
        d_ff=p["d_ff"], vocab_size=p["vocab"], qk_norm=True,
        remat="full")
    print(f"model: {cfg.param_count() / 1e6:.1f}M params")

    with tempfile.TemporaryDirectory() as td:
        fabric = Fabric(FabricSpec(sites=(
            SiteSpec("home", root=td + "/home"),
            SiteSpec("site", root=td + "/site"),
        )))
        net = fabric.network
        s = fabric.login("trainer",
                         mounts=[MountSpec("home/", ("home/scratch/",))])
        SyntheticCorpus(s.client, "home/data", seed=0,
                        vocab=cfg.vocab_size,
                        shard_tokens=max(p["seq"] * p["batch"] * 4, 8192)
                        ).materialize(4)
        pipe = DataPipeline(s.client, "home/data", cfg, batch=p["batch"],
                            seq=p["seq"], n_shards=4)
        run = RunConfig(model=cfg,
                        shape=ShapeConfig("train", "train", p["seq"],
                                          p["batch"]),
                        optim=OptimConfig(lr=3e-4, warmup_steps=20,
                                          total_steps=steps),
                        microbatches=p["micro"])
        ckpt = CheckpointManager(s.client, "home/ckpt")
        # inject a node failure a third of the way through
        monitor = FaultMonitor(n_workers=8, schedule=[
            FaultEvent(step=max(steps // 3, 2), worker=3, kind="crash")])
        trainer = Trainer(run, pipe, ckpt, monitor=monitor,
                          ckpt_every=max(steps // 10, 5))
        res = trainer.train(steps)
        print(f"steps={res.steps_run} restarts={res.restarts} "
              f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}")
        print(f"WAN clock {net.clock:.1f}s; checkpoints {res.checkpoints}")

        # cold-restart proof: a fresh trainer restores the newest manifest
        t2 = Trainer(run, pipe, ckpt)
        assert t2.restore_latest(), "no restorable checkpoint!"
        print(f"cold restore OK at step {t2.step}")


if __name__ == "__main__":
    main()

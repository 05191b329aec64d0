"""Training CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_14b --smoke \
        --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

On this CPU container use --smoke (reduced config).  On real hardware drop
--smoke and pass --mesh single|multi to train the full config on the
production mesh with the sharding rules from distributed/sharding.py.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data import make_dataset
from repro.launch.cache import use_compile_cache
from repro.models.api import build_model
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", choices=["none", "int8_ef"],
                    default="none")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    model = build_model(cfg)
    ds = make_dataset(cfg, seq_len=args.seq, global_batch=args.batch,
                      seed=args.seed)

    mesh = None
    batch_shardings = None
    if args.mesh != "none":
        from repro.distributed import sharding as shardlib
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
        batch_shape = jax.eval_shape(lambda: ds[0])
        batch_shardings = shardlib.logical_to_shardings(
            shardlib.batch_specs(batch_shape, mesh), mesh)

    tcfg = TrainerConfig(
        train=TrainConfig(
            optimizer=AdamWConfig(lr=args.lr),
            warmup_steps=max(1, args.steps // 10),
            total_steps=args.steps,
            microbatches=args.microbatches,
            compress_grads=args.compress_grads),
        ckpt_dir=args.ckpt_dir, max_steps=args.steps,
        ckpt_every=args.ckpt_every, seed=args.seed)
    trainer = Trainer(model, tcfg, ds, mesh=mesh,
                      batch_shardings=batch_shardings)
    out = trainer.run()
    losses = out["losses"]
    print(f"[train] {args.arch}: {len(losses)} steps, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"median step {np.median(trainer.step_times[2:]) * 1e3:.0f} ms, "
          f"stragglers {len(out['stragglers'])}")


if __name__ == "__main__":
    main()

"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains reduced configs end-to-end (the full
configs are exercised by the dry run); on a real pod the same entry point
drives the production mesh.
"""
from __future__ import annotations

import argparse
import dataclasses

from repro.configs.registry import ARCHS
from repro.core.storage_service import ObjectStore
from repro.data.pipeline import DataConfig
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--full-config", action="store_true",
                    help="use the published config (pod-scale; default is "
                         "the reduced smoke config)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = ARCHS[args.arch] if args.full_config else ARCHS[args.arch].reduced()
    cfg = dataclasses.replace(cfg, microbatches=min(cfg.microbatches,
                                                    args.global_batch))
    mesh = make_mesh(args.data, args.model)
    trainer = Trainer(
        cfg, mesh, ObjectStore(),
        DataConfig(seq_len=args.seq_len, global_batch=args.global_batch),
        opt_cfg=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                            total_steps=args.steps),
        tcfg=TrainerConfig(total_steps=args.steps,
                           checkpoint_every=args.checkpoint_every,
                           log_every=max(args.steps // 10, 1)))
    out = trainer.run()
    for m in out.get("metrics", []):
        print(f"step {m['step']:5d} loss {m['loss']:.4f}")
    print(out["status"], out.get("cost", ""))


if __name__ == "__main__":
    main()

"""Quickstart: train a reduced-config model end-to-end on CPU with the full
substrate — object-store checkpointing, burst-aware data pipeline, elastic
cost accounting.

    PYTHONPATH=src python examples/quickstart.py [--arch internlm2-1.8b]
"""
import argparse
import dataclasses

from repro.configs.registry import ARCHS
from repro.core.storage_service import ObjectStore
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_mesh
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    cfg = dataclasses.replace(ARCHS[args.arch].reduced(), microbatches=2)
    mesh = make_mesh(1, 1)
    store = ObjectStore()
    trainer = Trainer(
        cfg, mesh, store,
        DataConfig(seq_len=64, global_batch=8, seed=0),
        opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps),
        tcfg=TrainerConfig(total_steps=args.steps, checkpoint_every=10,
                           log_every=5))
    out = trainer.run()
    print(f"arch={args.arch} status={out['status']}")
    for m in out["metrics"]:
        print(f"  step {m['step']:4d} loss {m['loss']:.4f} "
              f"|grad| {m['grad_norm']:.3f}")
    first, last = out["metrics"][0]["loss"], out["metrics"][-1]["loss"]
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NOT improved'})")
    print("cost report:", out["cost"])
    print("checkpoints in store:",
          [k for k in store.list() if k.endswith("MANIFEST.json")])


if __name__ == "__main__":
    main()

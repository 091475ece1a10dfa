"""Elastic, fault-tolerant training demo (the paper's serverless execution
model applied to training):

  1. train on a 1-device mesh, checkpointing to the object store,
  2. PREEMPT the worker mid-run (simulated spot reclaim),
  3. resume on a *different* mesh width — restore re-shards the state —
  4. verify the loss trajectory continues where it left off.

    PYTHONPATH=src python examples/elastic_training.py
"""
import dataclasses

import jax

from repro.configs.registry import ARCHS
from repro.core.storage_service import ObjectStore
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_mesh
from repro.train.trainer import Preempted, Trainer, TrainerConfig


def main() -> None:
    cfg = dataclasses.replace(ARCHS["stablelm-3b"].reduced(),
                              microbatches=2)
    data = DataConfig(seq_len=32, global_batch=4, seed=7)
    store = ObjectStore()

    def preempt_at_12(step):
        if step == 12:
            print(f"  !! simulated preemption at step {step}")
            raise Preempted()

    print("phase 1: training on mesh (1,1), preempted at step 12")
    t1 = Trainer(cfg, make_mesh(1, 1), store, data,
                 tcfg=TrainerConfig(total_steps=20, checkpoint_every=5,
                                    log_every=2),
                 preemption_hook=preempt_at_12)
    out1 = t1.run()
    print(f"  status={out1['status']} resumable_from="
          f"{out1['resumable_from']}")

    n_dev = jax.device_count()
    mesh2 = make_mesh(n_dev, 1)
    print(f"phase 2: elastic restart on mesh ({n_dev},1) "
          f"— state re-sharded from the object store")
    t2 = Trainer(cfg, mesh2, store, data,
                 tcfg=TrainerConfig(total_steps=20, checkpoint_every=5,
                                    log_every=2))
    out2 = t2.run()
    print(f"  status={out2['status']}")
    for m in out2["metrics"]:
        print(f"  step {m['step']:4d} loss {m['loss']:.4f}")
    print("storage cost:", out2["cost"]["storage"])


if __name__ == "__main__":
    main()

"""Elastic, fault-tolerant training loop.

The trainer is the paper's execution model applied to training: workers are
stateless step executors; all durable state (params, optimizer, data
position) lives in the object store. Consequences implemented here:

  * checkpoint/restart — `run()` resumes from the latest manifest; a
    `PreemptionInjector` can kill the loop at arbitrary steps (tests do),
    and a fresh Trainer continues bit-exactly;
  * elastic re-shard — a restart may use a different mesh (data-parallel
    width); restore re-shards saved leaves onto the new topology;
  * cost accounting — every run reports elastic (fine-grained) vs
    provisioned (reserved pod) cost and the break-even utilisation, the
    paper's Table-6 economics applied to training jobs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import object_store_ckpt as ckpt
from repro.configs.base import ArchConfig
from repro.core import pricing
from repro.core.storage_service import ObjectStore
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch import steps as step_factory
from repro.train import optimizer as opt_mod


class Preempted(RuntimeError):
    pass


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 20
    checkpoint_every: int = 5
    seed: int = 0
    log_every: int = 5


class Trainer:
    def __init__(self, cfg: ArchConfig, mesh, store: ObjectStore,
                 data_cfg: DataConfig,
                 opt_cfg: opt_mod.AdamWConfig = opt_mod.AdamWConfig(),
                 tcfg: TrainerConfig = TrainerConfig(),
                 ckpt_prefix: str = "ckpt",
                 preemption_hook: Optional[Callable[[int], None]] = None):
        self.cfg = cfg
        self.mesh = mesh
        self.store = store
        self.tcfg = tcfg
        self.opt_cfg = opt_cfg
        self.ckpt_prefix = ckpt_prefix
        self.preemption_hook = preemption_hook
        self.pipeline = TokenPipeline(
            dataclasses.replace(data_cfg, vocab_size=cfg.vocab_size))
        self.step_fn, self._shardings = step_factory.make_train_step(
            cfg, mesh, opt_cfg, donate=False)
        self.metrics_log: list[dict] = []

    # ------------------------------------------------------------------
    def init_state(self):
        params = step_factory.init_params(self.cfg, self.mesh, self.tcfg.seed)
        opt_state = opt_mod.init_opt_state(params, self.opt_cfg)
        return params, opt_state

    def _restore_or_init(self):
        last = ckpt.latest_step(self.store, self.ckpt_prefix)
        params, opt_state = self.init_state()
        if last is None:
            return params, opt_state, 0
        p_shard, o_shard, _ = self._shardings
        params, _ = ckpt.restore_checkpoint(
            self.store, self.ckpt_prefix, params, step=last,
            shardings=p_shard)
        opt_state, _ = ckpt.restore_checkpoint(
            self.store, f"{self.ckpt_prefix}-opt", opt_state, step=last,
            shardings=o_shard)
        return params, opt_state, last

    # ------------------------------------------------------------------
    def run(self) -> dict:
        params, opt_state, start = self._restore_or_init()
        t0 = time.time()
        step = start
        try:
            for step in range(start, self.tcfg.total_steps):
                if self.preemption_hook:
                    self.preemption_hook(step)   # may raise Preempted
                batch = {k: jnp.asarray(v)
                         for k, v in self.pipeline.batch_at(step).items()}
                params, opt_state, metrics = self.step_fn(params, opt_state,
                                                          batch)
                if (step + 1) % self.tcfg.checkpoint_every == 0 or \
                        step + 1 == self.tcfg.total_steps:
                    self._checkpoint(params, opt_state, step + 1)
                if (step + 1) % self.tcfg.log_every == 0:
                    self.metrics_log.append(
                        {"step": step + 1,
                         "loss": float(metrics["loss"]),
                         "grad_norm": float(metrics["grad_norm"])})
        except Preempted:
            # Stateless worker death: durable state is already in the
            # store; a new Trainer picks up from the last manifest.
            return {"status": "preempted", "at_step": step,
                    "resumable_from":
                    ckpt.latest_step(self.store, self.ckpt_prefix) or 0}
        wall = time.time() - t0
        return {"status": "done", "steps": self.tcfg.total_steps,
                "final_loss": self.metrics_log[-1]["loss"]
                if self.metrics_log else None,
                "wall_s": wall, "cost": self.cost_report(wall),
                "metrics": self.metrics_log}

    def _checkpoint(self, params, opt_state, step: int) -> None:
        ckpt.save_checkpoint(self.store, self.ckpt_prefix, step, params)
        ckpt.save_checkpoint(self.store, f"{self.ckpt_prefix}-opt", step,
                             opt_state)

    # ------------------------------------------------------------------
    def cost_report(self, wall_s: float) -> dict:
        """Elastic vs reserved pod economics for this job (paper §5.2)."""
        chips = int(np.prod(self.mesh.devices.shape))
        h = wall_s / 3600.0
        elastic = pricing.tpu_pod_cost(chips, h, "on_demand")
        reserved = pricing.tpu_pod_cost(chips, h, "reserved")
        jobs_per_h_breakeven = reserved / max(elastic, 1e-12)
        return {"chips": chips, "elastic_usd": elastic,
                "reserved_usd_at_full_utilization": reserved,
                "utilization_breakeven":
                pricing.TPU_V5E_USD_PER_CHIP_H_RESERVED
                / pricing.TPU_V5E_USD_PER_CHIP_H,
                "storage": ckpt.checkpoint_cost(self.store)}

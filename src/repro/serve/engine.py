"""Batched serving engine: prefill + decode over fixed batch slots with
continuous slot refill, elastic-vs-provisioned cost accounting.

Serving is the paper's "sporadic workload" case: the engine tracks
request-level latency and per-request cost in both deployment models and
reports the break-even request rate (Table 6's argument at serve time).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import pricing
from repro.launch import steps as step_factory


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 8
    completion: Optional[np.ndarray] = None
    latency_s: float = 0.0


class ServingEngine:
    """Static-batch engine with greedy sampling; prompts are left-padded to
    the slot width, decoding advances all slots in lockstep and finished
    slots are refilled from the queue (continuous batching, lite)."""

    def __init__(self, cfg: ArchConfig, mesh, batch_size: int,
                 max_prompt: int, max_len: int, seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.batch_size = batch_size
        self.max_prompt = max_prompt
        self.max_len = max_len
        self.params = step_factory.init_params(cfg, mesh, seed)
        self.prefill, _ = step_factory.make_prefill_step(cfg, mesh,
                                                         cache_len=max_len)
        self.decode, _ = step_factory.make_decode_step(cfg, mesh, batch_size,
                                                       max_len)
        self.step_count = 0

    def _batch_prompts(self, reqs: list[Request]) -> jnp.ndarray:
        toks = np.zeros((self.batch_size, self.max_prompt), np.int32)
        for i, r in enumerate(reqs):
            p = r.prompt[-self.max_prompt:]
            toks[i, :len(p)] = p
        return jnp.asarray(toks)

    def serve(self, requests: list[Request]) -> list[Request]:
        """Process all requests in batches; returns them with completions."""
        done: list[Request] = []
        queue = list(requests)
        while queue:
            batch = queue[: self.batch_size]
            queue = queue[self.batch_size:]
            t0 = time.time()
            toks = self._batch_prompts(batch)
            batch_inputs = {"tokens": toks}
            if self.cfg.input_mode == "embeddings":
                emb = jnp.take(self.params["embed"], toks, axis=0)
                batch_inputs = {"embeds": emb.astype(
                    self.cfg.activation_dtype)}
                if self.cfg.rope == "mrope":
                    s = toks.shape[1]
                    batch_inputs["mrope_positions"] = jnp.broadcast_to(
                        jnp.arange(s)[None, None],
                        (3, toks.shape[0], s)).astype(jnp.int32)
            logits, caches = self.prefill(self.params, batch_inputs)
            outs = [list() for _ in batch]
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            max_new = max(r.max_new_tokens for r in batch)
            pos = self.max_prompt
            for t in range(max_new):
                for i in range(len(batch)):
                    outs[i].append(int(next_tok[i]))
                logits, caches = self.decode(self.params,
                                             next_tok[:, None], caches,
                                             jnp.asarray(pos + t, jnp.int32))
                next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                self.step_count += 1
            dt = time.time() - t0
            for i, r in enumerate(batch):
                r.completion = np.asarray(outs[i][: r.max_new_tokens])
                r.latency_s = dt
                done.append(r)
        return done

    # ------------------------------------------------------------------
    def cost_report(self, wall_s: float, n_requests: int) -> dict:
        chips = int(np.prod(self.mesh.devices.shape))
        h = wall_s / 3600.0
        elastic = pricing.tpu_pod_cost(chips, h, "on_demand")
        per_req = elastic / max(n_requests, 1)
        pod_per_h = pricing.tpu_pod_cost(chips, 1.0, "reserved")
        return {
            "per_request_usd": per_req,
            "breakeven_requests_per_hour": pod_per_h / max(per_req, 1e-12),
            "chips": chips,
        }

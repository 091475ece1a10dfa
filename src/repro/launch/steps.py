"""jit'd step factories: train_step, prefill_step, decode_step.

Each factory binds (arch config, mesh, rules) and returns a jit-compiled
function with explicit in/out shardings — the objects ``dryrun.py`` lowers
and the trainer/server execute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import transformer as tfm
from repro.models.attention import KVCache
from repro.models.common import set_activation_rules, split_tree
from repro.models.rglru import RglruState
from repro.models.rwkv6 import RwkvState
from repro.sharding import rules as shrules
from repro.train import optimizer as opt_mod


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------

def model_shardings(cfg: ArchConfig, mesh, rules: Optional[dict] = None):
    """(param_shapes, param_shardings) via eval_shape — no allocation."""
    tree = jax.eval_shape(functools.partial(tfm.init_model, cfg=cfg),
                          jax.random.PRNGKey(0))
    shapes, axes = split_tree(tree)
    shardings = jax.tree.map(
        lambda shaped, ax: NamedSharding(
            mesh, shrules.pspec_for(tuple(shaped.shape), ax, mesh, rules)),
        shapes, axes)
    return shapes, shardings


def init_params(cfg: ArchConfig, mesh, seed: int = 0):
    """Random parameters placed with their shardings on ``mesh``, float32
    leaves cast to the activation dtype. Initialised under ``jit``, so the
    float32 tree is never materialised whole before the cast (7.6 GB for a
    1.8B-parameter model, on a 16 GB chip)."""
    _, p_shard = model_shardings(cfg, mesh)

    def init(key):
        params, _ = split_tree(tfm.init_model(key, cfg))
        return jax.tree.map(
            lambda p: p.astype(cfg.activation_dtype)
            if p.dtype == jnp.float32 else p, params)

    return jax.jit(init, out_shardings=p_shard)(jax.random.PRNGKey(seed))


def opt_shardings(param_shardings, mesh):
    return opt_mod.OptState(
        NamedSharding(mesh, P()),
        jax.tree.map(lambda s: s, param_shardings),
        jax.tree.map(lambda s: s, param_shardings))


def dp_axes_for(batch: int, mesh):
    """(pod, data) axes when the global batch divides them; else None."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not axes:
        return None
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    size = 1
    for a in axes:
        size *= shape[a]
    if batch % size == 0:
        return axes
    if "data" in shape and batch % shape["data"] == 0:
        return ("data",)
    return None


def batch_shardings(cfg: ArchConfig, mesh, kind: str, batch: int,
                    act_rules: Optional[dict] = None):
    if act_rules is not None and act_rules.get("batch") is not None:
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        want = act_rules["batch"]
        want = want if isinstance(want, tuple) else (want,)
        axes = tuple(a for a in want if a in shape)
        size = 1
        for a in axes:
            size *= shape[a]
        dp = axes if (axes and (batch == 0 or batch % size == 0)) \
            else dp_axes_for(batch, mesh)
    else:
        dp = dp_axes_for(batch, mesh)
    out = {}
    if cfg.input_mode == "embeddings" and kind != "decode":
        out["embeds"] = NamedSharding(mesh, P(dp, None, None))
        if cfg.rope == "mrope":
            out["mrope_positions"] = NamedSharding(mesh, P(None, dp, None))
    else:
        out["tokens"] = NamedSharding(mesh, P(dp, None))
    if kind == "train":
        out["labels"] = NamedSharding(mesh, P(dp, None))
    return out


def cache_shardings(cache_shapes, mesh):
    """Sharding tree for the stacked per-segment caches."""
    tp = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def leaf(node):
        if isinstance(node, KVCache):
            return KVCache(
                NamedSharding(mesh, shrules.cache_pspec(node.k.shape, mesh)),
                NamedSharding(mesh, shrules.cache_pspec(node.v.shape, mesh)),
                NamedSharding(mesh, P(None)))
        if isinstance(node, RwkvState):
            dp = dp_axes_for(node.wkv.shape[1], mesh)
            h = node.wkv.shape[2]
            hs = "model" if tp > 1 and h % tp == 0 else None
            return RwkvState(
                NamedSharding(mesh, P(None, dp, hs, None, None)),
                NamedSharding(mesh, P(None, dp, None)),
                NamedSharding(mesh, P(None, dp, None)))
        if isinstance(node, RglruState):
            dp = dp_axes_for(node.h.shape[1], mesh)
            w = node.h.shape[-1]
            ws = "model" if tp > 1 and w % tp == 0 else None
            return RglruState(
                NamedSharding(mesh, P(None, dp, ws)),
                NamedSharding(mesh, P(None, dp, None, ws)))
        raise TypeError(type(node))

    return jax.tree.map(
        leaf, cache_shapes,
        is_leaf=lambda n: isinstance(n, (KVCache, RwkvState, RglruState)))


def _split_microbatches(batch: dict, micro: int):
    def split(key, leaf):
        axis = 1 if key == "mrope_positions" else 0
        b = leaf.shape[axis]
        assert b % micro == 0, (key, b, micro)
        new_shape = (leaf.shape[:axis] + (micro, b // micro)
                     + leaf.shape[axis + 1:])
        x = leaf.reshape(new_shape)
        return jnp.moveaxis(x, axis, 0)
    return {k: split(k, v) for k, v in batch.items()}


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ArchConfig, mesh, opt_cfg: opt_mod.AdamWConfig,
                    impl: str = "reference", rules: Optional[dict] = None,
                    donate: bool = True, global_batch: int = 0,
                    act_rules: Optional[dict] = None):
    """Returns (jit_fn, in_shardings tuple) — fwd+bwd over microbatches,
    grad accumulation, AdamW update."""
    act_rules = act_rules or shrules.activation_rules(mesh)
    _, p_shard = model_shardings(cfg, mesh, rules)
    o_shard = opt_shardings(p_shard, mesh)
    b_shard = batch_shardings(cfg, mesh, "train", global_batch,
                              act_rules)
    micro = cfg.microbatches

    def loss_fn(params, mb):
        loss, metrics = tfm.forward_train(params, cfg, mb, mesh=mesh,
                                          impl=impl)
        return loss, metrics

    def train_step(params, opt_state, batch):
        set_activation_rules(act_rules, mesh)
        if micro > 1:
            mbs = _split_microbatches(batch, micro)

            def acc_step(carry, mb):
                gacc, lacc = carry
                (loss, _), grads = jax.value_and_grad(loss_fn,
                                                      has_aux=True)(params, mb)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, grads)
                return (gacc, lacc + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (grads, loss_sum), _ = jax.lax.scan(acc_step, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / micro, grads)
            loss = loss_sum / micro
        else:
            (loss, _), grads = jax.value_and_grad(loss_fn,
                                                  has_aux=True)(params, batch)
        new_params, new_opt, metrics = opt_mod.apply_updates(
            params, grads, opt_state, opt_cfg)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    in_shardings = (p_shard, o_shard, b_shard)
    jit_fn = jax.jit(train_step, in_shardings=in_shardings,
                     out_shardings=(p_shard, o_shard, None),
                     donate_argnums=(0, 1) if donate else ())
    return jit_fn, in_shardings


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, mesh, cache_len: int,
                      impl: str = "reference", rules: Optional[dict] = None,
                      global_batch: int = 0,
                      act_rules: Optional[dict] = None):
    act_rules = act_rules or shrules.activation_rules(mesh)
    _, p_shard = model_shardings(cfg, mesh, rules)
    b_shard = batch_shardings(cfg, mesh, "prefill", global_batch,
                              act_rules)

    def prefill_step(params, batch):
        set_activation_rules(act_rules, mesh)
        logits, caches = tfm.forward_prefill(params, cfg, batch, cache_len,
                                             mesh=mesh, impl=impl)
        return logits, caches

    jit_fn = jax.jit(prefill_step, in_shardings=(p_shard, b_shard))
    return jit_fn, (p_shard, b_shard)


def make_decode_step(cfg: ArchConfig, mesh, batch_size: int, cache_len: int,
                     rules: Optional[dict] = None):
    act_rules = shrules.activation_rules(mesh)
    _, p_shard = model_shardings(cfg, mesh, rules)
    dp = dp_axes_for(batch_size, mesh)
    tok_shard = NamedSharding(mesh, P(dp, None))
    cache_shapes = jax.eval_shape(
        lambda: tfm.init_cache(cfg, batch_size, cache_len,
                               cfg.activation_dtype))
    c_shard = cache_shardings(cache_shapes, mesh)

    def decode_step(params, tokens, caches, position):
        set_activation_rules(act_rules, mesh)
        logits, new_caches = tfm.forward_decode(params, cfg, tokens, caches,
                                                position, mesh=mesh)
        return logits, new_caches

    jit_fn = jax.jit(decode_step,
                     in_shardings=(p_shard, tok_shard, c_shard, None),
                     out_shardings=(None, c_shard),
                     donate_argnums=(2,))
    return jit_fn, (p_shard, tok_shard, c_shard)

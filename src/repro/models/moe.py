"""Fine-grained mixture-of-experts with capacity-based token-choice routing.

Two execution paths with identical math:
  * single-device (smoke tests, kernels oracle): dispatch/compute/combine
    on the local token set;
  * expert-parallel (production): ``jax.shard_map`` over the (data, model)
    mesh — tokens sharded batch x sequence, experts sharded over 'model',
    explicit ``all_to_all`` exchanges (GShard-style EP). The collective
    schedule is therefore visible to the roofline analysis.

Routing: softmax router, top-k per token (optionally renormalized — Qwen3),
capacity C = ceil(k * T_local / E * capacity_factor) with token-priority
dropping, plus the standard load-balance auxiliary loss. Shared experts
(DeepSeekMoE) run densely beside the routed experts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, MoEConfig
from repro.models.common import Param, dense_init, shard, silu


def init_moe(key, cfg: ArchConfig):
    mo = cfg.moe
    d, e, f = cfg.d_model, mo.num_experts, mo.expert_d_ff
    ks = jax.random.split(key, 7)
    p = {
        "w_router": dense_init(ks[0], (d, e), ("embed", "experts"),
                               dtype=jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), ("experts", "embed", "ff")),
        "w_up": dense_init(ks[2], (e, d, f), ("experts", "embed", "ff")),
        "w_down": dense_init(ks[3], (e, f, d), ("experts", "ff", "embed"),
                             fan_in=f),
    }
    if mo.num_shared_experts:
        sf = mo.shared_d_ff or mo.expert_d_ff * mo.num_shared_experts
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, sf), ("embed", "ff")),
            "w_up": dense_init(ks[5], (d, sf), ("embed", "ff")),
            "w_down": dense_init(ks[6], (sf, d), ("ff", "embed"), fan_in=sf),
        }
    return p


# ---------------------------------------------------------------------------
# Routing + dispatch/combine (local token set)
# ---------------------------------------------------------------------------

def _route(params, x2d, mo: MoEConfig, norm_topk: bool):
    """x2d: (T, D) -> gates (T,k), idx (T,k), aux loss scalar."""
    logits = x2d.astype(jnp.float32) @ params["w_router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gates, idx = jax.lax.top_k(probs, mo.top_k)                # (T, k)
    if norm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch/GShard load-balance loss: E * sum_e f_e * p_e.
    e = mo.num_experts
    density = jnp.zeros((e,), jnp.float32)
    density = density.at[idx.reshape(-1)].add(1.0)
    density = density / jnp.maximum(density.sum(), 1.0)
    mean_probs = probs.mean(axis=0)
    aux = e * jnp.sum(density * mean_probs)
    return gates, idx, aux


def _dispatch(x2d, gates, idx, capacity: int, num_experts: int):
    """Token-priority capacity dispatch.

    Returns xb (E, C, D), and per-slot (flat position, keep, gate) used by
    combine. Positions are assigned in token order; overflow tokens drop.
    """
    t, k = idx.shape
    onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(t * k, num_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat                  # (T*k, E)
    pos = jnp.take_along_axis(
        pos_flat.reshape(t, k, num_experts),
        idx[..., None], axis=-1)[..., 0]                        # (T, k)
    keep = pos < capacity
    slot = idx * capacity + jnp.where(keep, pos, 0)             # (T, k)
    xb = jnp.zeros((num_experts * capacity, x2d.shape[-1]), x2d.dtype)
    for j in range(k):   # k is small and static — k scatters of (T, D)
        contrib = jnp.where(keep[:, j, None], x2d, 0)
        xb = xb.at[slot[:, j]].add(contrib, mode="drop")
    return xb.reshape(num_experts, capacity, -1), slot, keep


def _combine(yb, slot, keep, gates, out_dtype):
    """Gather expert outputs back to tokens with gate weighting."""
    t, k = slot.shape
    y2d = yb.reshape(-1, yb.shape[-1])
    out = jnp.zeros((t, yb.shape[-1]), jnp.float32)
    for j in range(k):
        rows = y2d[slot[:, j]].astype(jnp.float32)
        out = out + rows * (gates[:, j] * keep[:, j])[:, None]
    return out.astype(out_dtype)


def _expert_ffn(params, xb, use_kernel: bool = False):
    """xb: (E_local, C', D) grouped matmuls over stacked expert weights."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.moe_grouped_ffn(xb, params["w_gate"], params["w_up"],
                                    params["w_down"])
    gate = jnp.einsum("ecd,edf->ecf", xb, params["w_gate"])
    up = jnp.einsum("ecd,edf->ecf", xb, params["w_up"])
    h = silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _capacity(tokens: int, mo: MoEConfig) -> int:
    c = int(-(-mo.top_k * tokens * mo.capacity_factor // mo.num_experts))
    return max(c, 1)


# ---------------------------------------------------------------------------
# Public layer
# ---------------------------------------------------------------------------

def moe_layer(params, x, cfg: ArchConfig, *, mesh=None,
              use_kernel: bool = False):
    """x: (B, S, D) -> (y, aux_loss). EP path when ``mesh`` has a 'model'
    axis; otherwise single-device math (identical numerics)."""
    mo = cfg.moe
    b, s, d = x.shape
    norm_topk = mo.norm_topk

    if mesh is not None and "model" in mesh.axis_names:
        y, aux = _moe_ep(params, x, cfg, mesh, norm_topk, use_kernel)
    else:
        x2d = x.reshape(b * s, d)
        gates, idx, aux = _route(params, x2d, mo, norm_topk)
        cap = _capacity(b * s, mo)
        xb, slot, keep = _dispatch(x2d, gates, idx, cap, mo.num_experts)
        yb = _expert_ffn(params, xb, use_kernel)
        y = _combine(yb, slot, keep, gates, x.dtype).reshape(b, s, d)

    if mo.num_shared_experts:
        sp = params["shared"]
        gate = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
        up = jnp.einsum("bsd,df->bsf", x, sp["w_up"])
        y = y + jnp.einsum("bsf,fd->bsd", silu(gate) * up, sp["w_down"])
    return y, aux


def _moe_ep(params, x, cfg: ArchConfig, mesh, norm_topk: bool,
            use_kernel: bool):
    """Expert parallelism over the 'model' axis.

    Train/prefill (S divisible by tp): tokens sharded batch x sequence,
    dispatch buffers exchanged with two all_to_alls (GShard EP).
    Decode (S=1): dispatch is computed per data-shard, each model rank runs
    its expert slice, partial combines are psum-reduced — no all_to_all on
    a 1-token sequence.
    """
    mo = cfg.moe
    P = jax.sharding.PartitionSpec
    tp = mesh.shape["model"]
    assert mo.num_experts % tp == 0, (mo.num_experts, tp)
    dp_axes = ("pod", "data") if "pod" in mesh.axis_names else "data"
    all_axes = tuple(mesh.axis_names)
    b, s, _ = x.shape

    def pmean_all(v):
        for ax in all_axes:
            v = jax.lax.pmean(v, ax)
        return v

    if s % tp == 0:
        def local(x_loc, wr, wg, wu, wd):
            bl, sl, d = x_loc.shape
            x2d = x_loc.reshape(bl * sl, d)
            gates, idx, aux = _route({"w_router": wr}, x2d, mo, norm_topk)
            cap = _capacity(bl * sl, mo)
            xb, slot, keep = _dispatch(x2d, gates, idx, cap, mo.num_experts)
            # (E, C, D) -> (E/tp, tp*C, D): every device receives the slices
            # bound for its local experts from every peer.
            xb = jax.lax.all_to_all(xb, "model", split_axis=0, concat_axis=1,
                                    tiled=True)
            yb = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd},
                             xb, use_kernel)
            yb = jax.lax.all_to_all(yb, "model", split_axis=1, concat_axis=0,
                                    tiled=True)
            y = _combine(yb, slot, keep, gates, x_loc.dtype)
            return y.reshape(bl, sl, d), pmean_all(aux)

        in_x = P(dp_axes, "model", None)
        out_x = P(dp_axes, "model", None)
    else:
        def local(x_loc, wr, wg, wu, wd):
            bl, sl, d = x_loc.shape
            x2d = x_loc.reshape(bl * sl, d)
            gates, idx, aux = _route({"w_router": wr}, x2d, mo, norm_topk)
            cap = _capacity(bl * sl, mo)
            xb, slot, keep = _dispatch(x2d, gates, idx, cap, mo.num_experts)
            e_local = mo.num_experts // tp
            rank = jax.lax.axis_index("model")
            xb_loc = jax.lax.dynamic_slice_in_dim(xb, rank * e_local,
                                                  e_local, axis=0)
            yb_loc = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd},
                                 xb_loc, use_kernel)
            # Partial combine against the local expert slice only, then
            # reduce partial token outputs across the model axis.
            local_slot = slot - rank * e_local * cap
            in_range = (slot >= rank * e_local * cap) & \
                (slot < (rank + 1) * e_local * cap)
            y = _combine(yb_loc, jnp.where(in_range, local_slot, 0),
                         keep & in_range, gates, jnp.float32)
            y = jax.lax.psum(y, "model").astype(x_loc.dtype)
            return y.reshape(bl, sl, d), pmean_all(aux)

        in_x = P(dp_axes, None, None)
        out_x = P(dp_axes, None, None)

    # NOTE on a refuted design (EXPERIMENTS.md §Perf): sharding the expert
    # d_ff over 'data' inside this shard_map (expert-TP, to avoid the FSDP
    # weight gathers) is unsound here — the down-projection psum over
    # 'data' would reduce across *different token shards* (batch is
    # data-sharded). Expert-TP needs a mesh axis on which tokens are
    # replicated; on this 2D mesh there is none.
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(in_x,
                  P(None, None),                       # router replicated
                  P("model", None, None),              # experts sharded,
                  P("model", None, None),              # d/f gathered (FSDP)
                  P("model", None, None)),
        out_specs=(out_x, P()))
    return fn(x, params["w_router"], params["w_gate"], params["w_up"],
              params["w_down"])

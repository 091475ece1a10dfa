"""Model assembly: segment-stacked decoder with scan-over-layers.

Layers are grouped into *segments* of a repeating block pattern (uniform
stacks for most archs; (rec, rec, local)x8 + (rec, rec) for RecurrentGemma;
a leading dense layer for DeepSeekMoE). Each segment's parameters are
stacked on a leading 'layers' axis and executed with ``jax.lax.scan`` so
compile time and HLO size are O(1) in depth; ``jax.checkpoint`` on the scan
body implements per-block activation rematerialization.

Three entry points: ``forward_train`` (loss), ``forward_prefill`` (last
logits + cache), ``forward_decode`` (one-token step).

Block kinds:
  attn    — RMSNorm -> GQA attention -> RMSNorm -> SwiGLU
  local   — same, sliding-window attention (cfg.window)
  moe     — RMSNorm -> GQA attention -> RMSNorm -> MoE (+ shared experts)
  dense0  — 'attn' with the MoE config's dense_d_ff (DeepSeekMoE layer 0)
  rwkv    — RWKV-6 time-mix -> channel-mix (attention-free)
  rec     — RG-LRU recurrent block -> SwiGLU
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.attention import KVCache
from repro.models.common import (Param, embed_init, ones_init, shard,
                                 split_tree)


# ---------------------------------------------------------------------------
# Segments
# ---------------------------------------------------------------------------

def compute_segments(cfg: ArchConfig) -> list[tuple[tuple[str, ...], int]]:
    kinds = cfg.layer_kinds()
    if cfg.moe and cfg.moe.first_k_dense:
        for i in range(cfg.moe.first_k_dense):
            kinds[i] = "dense0"
    segs: list[tuple[tuple[str, ...], int]] = []
    i, n = 0, len(kinds)
    while i < n:
        best = (1, 1)
        for ul in (1, 2, 3, 4):
            unit = kinds[i:i + ul]
            if len(unit) < ul:
                break
            r = 1
            while kinds[i + r * ul: i + (r + 1) * ul] == unit:
                r += 1
            # Only repeating units justify a scan stack; a one-shot long
            # unit would glue heterogeneous layers into one segment.
            if r > 1 and r * ul > best[0] * best[1]:
                best = (ul, r)
        if best == (1, 1):
            # Run-length of the single kind at i.
            r = 1
            while i + r < n and kinds[i + r] == kinds[i]:
                r += 1
            best = (1, r)
        ul, r = best
        segs.append((tuple(kinds[i:i + ul]), r))
        i += ul * r
    assert sum(len(u) * r for u, r in segs) == n
    return segs


def _init_sublayer(key, kind: str, cfg: ArchConfig):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": ones_init((cfg.d_model,), ("embed",))}
    if kind in ("attn", "local", "moe", "dense0"):
        p["attn"] = attn_mod.init_attention(ks[0], cfg)
        p["ln2"] = ones_init((cfg.d_model,), ("embed",))
        if kind == "moe":
            p["ffn"] = moe_mod.init_moe(ks[1], cfg)
        elif kind == "dense0":
            p["ffn"] = mlp_mod.init_mlp(ks[1], cfg.d_model,
                                        cfg.moe.dense_d_ff)
        else:
            p["ffn"] = mlp_mod.init_mlp(ks[1], cfg.d_model, cfg.d_ff)
    elif kind == "rwkv":
        p["tmix"] = rwkv_mod.init_rwkv(ks[0], cfg)
        p["ln2"] = ones_init((cfg.d_model,), ("embed",))
        p["cmix"] = rwkv_mod.init_rwkv_channel_mix(ks[1], cfg)
    elif kind == "rec":
        p["rgl"] = rglru_mod.init_rglru(ks[0], cfg)
        p["ln2"] = ones_init((cfg.d_model,), ("embed",))
        p["ffn"] = mlp_mod.init_mlp(ks[1], cfg.d_model, cfg.d_ff)
    else:
        raise ValueError(kind)
    return p


def _init_stacked(keys, kind: str, cfg: ArchConfig):
    """One sublayer's parameters per key, stacked on a leading 'layers'
    axis. ``vmap`` keeps the program one layer wide (a 24-layer init
    compiles as fast as one layer) and draws exactly what per-key calls
    would."""
    tree = jax.vmap(lambda k: _init_sublayer(k, kind, cfg))(keys)
    return jax.tree.map(lambda p: Param(p.value, ("layers",) + p.axes),
                        tree, is_leaf=lambda x: isinstance(x, Param))


def init_model(rng, cfg: ArchConfig):
    """Returns a Param tree (values + logical axes; use common.split_tree)."""
    segs = compute_segments(cfg)
    k_embed, k_head, rng = jax.random.split(rng, 3)
    params: dict[str, Any] = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model),
                            ("vocab", "embed_table")),
        "ln_f": ones_init((cfg.d_model,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(k_head, (cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"))
    segments = []
    for unit, repeats in segs:
        stacked = {}
        for j, kind in enumerate(unit):
            keys = jax.random.split(jax.random.fold_in(rng, len(segments)
                                                       * 8 + j), repeats)
            stacked[f"sub{j}"] = _init_stacked(keys, kind, cfg)
        segments.append(stacked)
        rng = jax.random.fold_in(rng, 7)
    params["segments"] = segments
    return params


# ---------------------------------------------------------------------------
# Sub-layer application (single layer, full-sequence or decode)
# ---------------------------------------------------------------------------

def _apply_sublayer(p, x, kind: str, cfg: ArchConfig, positions, *, mesh,
                    impl: str, mode: str, cache=None, position=None):
    """Returns (x, aux, new_cache)."""
    from repro.models.common import rms_norm
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    window = cfg.window if kind == "local" else 0
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("attn", "local", "moe", "dense0"):
        if mode == "train":
            a = attn_mod.attention(p["attn"], h, cfg, positions,
                                   window=window, impl=impl)
        elif mode == "prefill":
            a, new_cache = attn_mod.attention_prefill(
                p["attn"], h, cfg, positions, cache_len=cache["len"],
                window=window, impl=impl)
        else:
            a, new_cache = attn_mod.attention_decode(
                p["attn"], h, cfg, position, cache, window=window)
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if kind == "moe":
            f, aux = moe_mod.moe_layer(p["ffn"], h2, cfg, mesh=mesh,
                                       use_kernel=(impl == "flash_moe"))
        else:
            f = mlp_mod.mlp(p["ffn"], h2)
        x = x + f
    elif kind == "rwkv":
        if mode == "decode":
            a, new_cache = rwkv_mod.rwkv_time_mix_decode(p["tmix"], h, cfg,
                                                         cache)
        else:
            a, new_cache = rwkv_mod.rwkv_time_mix(
                p["tmix"], h, cfg, None, use_kernel=(impl == "flash"))
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x_prev_c = new_cache.x_prev_c if mode == "decode" \
            else jnp.zeros((x.shape[0], x.shape[-1]), x.dtype)
        c = rwkv_mod.rwkv_channel_mix(p["cmix"], h2, x_prev_c)
        if new_cache is not None:
            new_cache = rwkv_mod.RwkvState(new_cache.wkv, new_cache.x_prev_t,
                                           h2[:, -1])
        x = x + c
    elif kind == "rec":
        if mode == "decode":
            a, new_cache = rglru_mod.rglru_block_decode(p["rgl"], h, cfg,
                                                        cache)
        else:
            a, new_cache = rglru_mod.rglru_block(
                p["rgl"], h, cfg, None, use_kernel=(impl == "flash"))
        x = x + a
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_mod.mlp(p["ffn"], h2)
    else:
        raise ValueError(kind)
    x = shard(x, ("batch", "seq", "embed"))
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Cache init (prefill allocates; decode consumes)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, cache_len: int,
               dtype) -> list[Any]:
    """Abstract per-segment stacked cache for decode entry (ShapeDtype-
    compatible: call under jax.eval_shape for specs)."""
    segs = compute_segments(cfg)
    hd = cfg.head_dim
    caches = []
    for unit, repeats in segs:
        seg_cache = {}
        for j, kind in enumerate(unit):
            if kind in ("attn", "moe", "dense0"):
                size = cache_len
                seg_cache[f"sub{j}"] = KVCache(
                    jnp.zeros((repeats, batch, size, cfg.num_kv_heads, hd),
                              dtype),
                    jnp.zeros((repeats, batch, size, cfg.num_kv_heads, hd),
                              dtype),
                    jnp.zeros((repeats,), jnp.int32))
            elif kind == "local":
                size = min(cfg.window, cache_len)
                seg_cache[f"sub{j}"] = KVCache(
                    jnp.zeros((repeats, batch, size, cfg.num_kv_heads, hd),
                              dtype),
                    jnp.zeros((repeats, batch, size, cfg.num_kv_heads, hd),
                              dtype),
                    jnp.zeros((repeats,), jnp.int32))
            elif kind == "rwkv":
                h = cfg.d_model // cfg.recurrent.head_dim
                seg_cache[f"sub{j}"] = rwkv_mod.RwkvState(
                    jnp.zeros((repeats, batch, h, cfg.recurrent.head_dim,
                               cfg.recurrent.head_dim), jnp.float32),
                    jnp.zeros((repeats, batch, cfg.d_model), dtype),
                    jnp.zeros((repeats, batch, cfg.d_model), dtype))
            elif kind == "rec":
                w = cfg.recurrent.lru_width or cfg.d_model
                seg_cache[f"sub{j}"] = rglru_mod.RglruState(
                    jnp.zeros((repeats, batch, w), jnp.float32),
                    jnp.zeros((repeats, batch,
                               cfg.recurrent.conv_width - 1, w), dtype))
        caches.append(seg_cache)
    return caches


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ArchConfig, batch: dict):
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.activation_dtype)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x.astype(cfg.activation_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    b, s = x.shape[0], x.shape[1]
    if cfg.rope == "mrope":
        positions = batch.get("mrope_positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None, None],
                                         (3, b, s))
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return x, positions


def _run_segments(params, cfg: ArchConfig, x, positions, *, mesh, impl,
                  mode: str, caches=None, position=None, cache_len: int = 0):
    """Scan over stacked segments. Returns (x, total_aux, new_caches)."""
    segs = compute_segments(cfg)
    total_aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, ((unit, repeats), seg_params) in enumerate(
            zip(segs, params["segments"])):

        def body(carry, xs):
            x, aux = carry
            layer_p = xs["params"]
            layer_c = xs.get("cache")
            ys = {}
            for j, kind in enumerate(unit):
                c_in = None
                if mode == "prefill":
                    c_in = {"len": cache_len}
                elif mode == "decode":
                    c_in = layer_c[f"sub{j}"]
                x, a, c_out = _apply_sublayer(
                    layer_p[f"sub{j}"], x, kind, cfg, positions, mesh=mesh,
                    impl=impl, mode=mode, cache=c_in, position=position)
                aux = aux + a
                if c_out is not None:
                    ys[f"sub{j}"] = c_out
            return (x, aux), ys

        if cfg.remat != "none" and mode == "train":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        xs = {"params": seg_params}
        if mode == "decode":
            xs["cache"] = caches[si]
        (x, total_aux), ys = jax.lax.scan(body, (x, total_aux), xs)
        if mode in ("prefill", "decode"):
            new_caches.append(ys)
    return x, total_aux, new_caches


def _lm_head(params, cfg: ArchConfig, x):
    from repro.models.common import rms_norm
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, w).astype(jnp.float32)
    return shard(logits, ("batch", "seq", "vocab"))


def forward_train(params, cfg: ArchConfig, batch: dict, *, mesh=None,
                  impl: str = "reference"):
    """Returns (loss, metrics). batch: tokens|embeds, labels, [positions]."""
    x, positions = _embed_inputs(params, cfg, batch)
    x, aux, _ = _run_segments(params, cfg, x, positions, mesh=mesh,
                              impl=impl, mode="train")
    logits = _lm_head(params, cfg, x)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if cfg.moe:
        loss = loss + cfg.moe.router_aux_weight * aux
    return loss, {"nll": loss, "aux": aux}


def forward_prefill(params, cfg: ArchConfig, batch: dict, cache_len: int, *,
                    mesh=None, impl: str = "reference"):
    """Returns (last_token_logits, caches)."""
    x, positions = _embed_inputs(params, cfg, batch)
    x, _, caches = _run_segments(params, cfg, x, positions, mesh=mesh,
                                 impl=impl, mode="prefill",
                                 cache_len=cache_len)
    logits = _lm_head(params, cfg, x[:, -1:])
    return logits[:, 0], caches


def forward_decode(params, cfg: ArchConfig, tokens, caches, position, *,
                   mesh=None, impl: str = "reference"):
    """One decode step. tokens: (B, 1) int32; position: () int32 scalar.
    Returns (logits (B, V), new_caches)."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.activation_dtype)
    x = shard(x, ("batch", "seq", "embed"))
    positions = None  # per-kind decode paths build their own positions
    x, _, new_caches = _run_segments(params, cfg, x, positions, mesh=mesh,
                                     impl="reference", mode="decode",
                                     caches=caches, position=position)
    logits = _lm_head(params, cfg, x)
    return logits[:, 0], new_caches

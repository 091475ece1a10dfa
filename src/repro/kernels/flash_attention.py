"""Pallas TPU flash attention (GQA / causal / sliding-window).

Layout: q (B, H, Sq, D); k, v (B, Hkv, Skv, D) — heads-major so a (block_q,
D) Q tile and (block_k, D) KV tiles live in VMEM per grid step and matmuls
are MXU-shaped. Online-softmax accumulators (m, l, acc) persist in VMEM
scratch across the KV-block grid dimension (minor-most, sequential).

Grid: (B, H, Sq/block_q, Skv/block_k). GQA is expressed in the K/V
BlockSpec index maps (kv head = h // group), so no repeated KV in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu

NEG_INF = -2.3819763e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 causal: bool, window: int, block_q: int, block_k: int,
                 sm_scale: float, kv_steps: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # Guard fully-masked rows (all -inf): exp(NEG_INF - NEG_INF) -> use 0.
    safe = m_new > NEG_INF / 2
    alpha = jnp.where(safe, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(safe, jnp.exp(s - m_new), 0.0)    # (bq, bk)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ik == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_hmajor(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, H, Sq, D)."""
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    kv_steps = skv // block_k
    grid = (b, h, sq // block_q, kv_steps)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, block_q=block_q,
        block_k=block_k, sm_scale=1.0 / (d ** 0.5), kv_steps=kv_steps)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m: running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # l: running denom
            pltpu.VMEM((block_q, d), jnp.float32),   # acc: running numerator
        ],
        compiler_params=tpu.params(("parallel", "parallel", "parallel",
                                    "arbitrary")),
        interpret=interpret,
    )(q, k, v)

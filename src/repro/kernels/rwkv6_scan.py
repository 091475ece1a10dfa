"""Pallas chunked RWKV-6 WKV scan.

One grid step processes one (batch, head, chunk) tile with the factorized
chunk form (two MXU matmuls + decay elementwise); the (K, V) state persists
in VMEM scratch across the chunk (minor-most, sequential) dimension.

Factorized intra-chunk form (see models/rwkv6.py for the derivation):
  q'_t = r_t * exp(excl_t),  k'_i = k_i * exp(-incl_i)
  scores = tril(q' k'^T, -1) + diag(r_t . u . k_t)
  o = scores @ v + (r * exp(excl)) @ S
  S' = exp(total) * S + (k * exp(total - incl))^T v

The exp(-incl) factor bounds this kernel to moderate per-chunk decay mass
(|sum log w| over a chunk within fp32 exp range) — holds for trained RWKV
decays at chunk <= 64; the oracle (kernels.ref.rwkv6_chunked_ref) uses the
exact pairwise form and is used to verify that regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref,
                 sout_ref, s_scr, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    r = r_ref[0, 0].astype(jnp.float32)            # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)            # (C, V)
    lw = lw_ref[0, 0].astype(jnp.float32)          # (C, K)
    u = u_ref[0].astype(jnp.float32)               # (K,)

    incl = jnp.cumsum(lw, axis=0)
    excl = incl - lw
    total = incl[-1:]                               # (1, K)

    s = s_scr[...]                                  # (K, V)
    qp = r * jnp.exp(excl)
    kp = k * jnp.exp(-incl)
    scores = jax.lax.dot_general(qp, kp, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(ii < ti, scores, 0.0)
    bonus = jnp.sum(r * u[None, :] * k, axis=1, keepdims=True)  # (C, 1)
    o = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o += bonus * v
    o += jax.lax.dot_general(qp, s, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)

    kd = k * jnp.exp(total - incl)                  # (C, K)
    s_scr[...] = s * jnp.exp(total).T + jax.lax.dot_general(
        kd, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ic == n_chunks - 1)
    def _final():
        sout_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_hmajor(r, k, v, log_w, u, s0, *, chunk: int = 64,
                      interpret: bool = False):
    """r/k/v/log_w: (B, H, S, K|V); u: (H, K); s0: (B, H, K, V) fp32.
    Returns (o (B, H, S, V), s_final (B, H, K, V))."""
    b, h, s, kd = r.shape
    vd = v.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    grid = (b, h, nc)
    kernel = functools.partial(_rwkv_kernel, chunk=chunk, n_chunks=nc)

    o, s_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, vd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, kd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, kd), lambda b_, h_, c: (h_, 0)),
            pl.BlockSpec((1, 1, kd, vd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, vd), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, kd, vd), lambda b_, h_, c: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, vd), r.dtype),
            jax.ShapeDtypeStruct((b, h, kd, vd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kd, vd), jnp.float32)],
        compiler_params=tpu.params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, log_w, u, s0)
    return o, s_out

"""jit'd public wrappers for the Pallas kernels.

Layout adaptation between model convention (B, S, H, D) and kernel
convention (B, H, S, D) happens here; ``tpu.interpret()`` picks the
Pallas interpreter off the TPU and Mosaic on it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm as _moe_gmm
from repro.kernels import rglru_scan as _rglru
from repro.kernels import rwkv6_scan as _rwkv6
from repro.kernels import tpu


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """Model layout: q (B, S, H, D); k, v (B, S, Hkv, D)."""
    out = fa.flash_attention_hmajor(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=tpu.interpret())
    return out.transpose(0, 2, 1, 3)


def gmm(x, w, **kw):
    return _moe_gmm.gmm(x, w, interpret=tpu.interpret(), **kw)


def moe_grouped_ffn(x, w_gate, w_up, w_down):
    gate = gmm(x, w_gate)
    up = gmm(x, w_up)
    h = gate * jax.nn.sigmoid(gate) * up
    return gmm(h, w_down)


def rwkv6_scan(r, k, v, log_w, u, s0, *, chunk: int = 64):
    """Model layout: r/k/v/log_w (B, S, H, K); s0 (B, H, K, V)."""
    s = r.shape[1]
    pad = (-s) % chunk
    tr = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))
                           ).transpose(0, 2, 1, 3)
    o, s_out = _rwkv6.rwkv6_scan_hmajor(
        tr(r), tr(k), tr(v), tr(log_w), u, s0, chunk=chunk,
        interpret=tpu.interpret())
    return o.transpose(0, 2, 1, 3)[:, :s], s_out


def rglru_scan(log_a, b_in, h0, *, chunk: int = 256):
    s = log_a.shape[1]
    pad = (-s) % chunk
    if pad:
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0)))
    h_all, h_last = _rglru.rglru_scan_blocked(
        log_a, b_in, h0, chunk=chunk, interpret=tpu.interpret())
    return h_all[:, :s], h_last

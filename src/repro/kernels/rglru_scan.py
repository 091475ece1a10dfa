"""Pallas blocked RG-LRU linear scan.

h_t = exp(log_a_t) * h_{t-1} + b_t, evaluated chunk-by-chunk: the grid is
(B, W/block_w, S/chunk) with the chunk dimension minor-most (sequential)
and the (1, block_w) hidden state persisted in VMEM scratch. Within a chunk
the recurrence runs as an in-kernel scan over rows — the vector parallelism
is across the W lanes (and the B / W-block grid axes), which is how an
elementwise recurrence maps to the TPU VPU. Sequential-in-time evaluation
is numerically exact for arbitrarily strong decays (no exp(+cumsum)
factorization), unlike a log-space parallel form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu


def _rglru_kernel(la_ref, b_ref, h0_ref, o_ref, hlast_ref, h_scr, *,
                  n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[...]

    la = la_ref[0].astype(jnp.float32)        # (chunk, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(h, row):
        la_t, b_t = row
        h = jnp.exp(la_t) * h + b_t
        return h, h

    h_last, h_all = jax.lax.scan(step, h_scr[0], (la, b))
    o_ref[0] = h_all.astype(o_ref.dtype)
    h_scr[0] = h_last

    @pl.when(ic == n_chunks - 1)
    def _final():
        hlast_ref[...] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_w", "interpret"))
def rglru_scan_blocked(log_a, b_in, h0, *, chunk: int = 256,
                       block_w: int = 512, interpret: bool = False):
    """log_a, b_in: (B, S, W) fp32; h0: (B, W) -> (h_all (B,S,W), h_last)."""
    b, s, w = log_a.shape
    chunk = min(chunk, s)
    block_w = min(block_w, w)
    assert s % chunk == 0 and w % block_w == 0
    nc = s // chunk
    grid = (b, w // block_w, nc)
    kernel = functools.partial(_rglru_kernel, n_chunks=nc)

    h_all, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, iw, ic: (b_, ic, iw)),
            pl.BlockSpec((1, chunk, block_w), lambda b_, iw, ic: (b_, ic, iw)),
            pl.BlockSpec((1, block_w), lambda b_, iw, ic: (b_, iw)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b_, iw, ic: (b_, ic, iw)),
            pl.BlockSpec((1, block_w), lambda b_, iw, ic: (b_, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, w), jnp.float32),
            jax.ShapeDtypeStruct((b, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=tpu.params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b_in, h0)
    return h_all, h_last

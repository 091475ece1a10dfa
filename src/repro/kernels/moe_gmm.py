"""Pallas grouped matmul for MoE expert FFNs.

Computes y[e] = x[e] @ w[e] over stacked experts: x (E, C, D), w (E, D, F).
Grid (E, C/bc, F/bf, D/bd) with a fp32 VMEM accumulator persisted across
the contraction (minor-most) dimension; block shapes default to
MXU-aligned 128 tiles (shrunk to the actual dims for small tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu


def _gmm_kernel(x_ref, w_ref, y_ref, acc_scr, *, d_steps: int):
    kd = pl.program_id(3)

    @pl.when(kd == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)               # (bc, bd)
    w = w_ref[0].astype(jnp.float32)               # (bd, bf)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == d_steps - 1)
    def _finalize():
        y_ref[0] = acc_scr[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def gmm(x, w, *, block_c: int = 128, block_f: int = 128, block_d: int = 512,
        interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    e, c, d = x.shape
    f = w.shape[-1]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0
    d_steps = d // block_d
    grid = (e, c // block_c, f // block_f, d_steps)

    kernel = functools.partial(_gmm_kernel, d_steps=d_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e_, ic, jf, kd: (e_, ic, kd)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e_, ic, jf, kd: (e_, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e_, ic, jf, kd: (e_, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=tpu.params(("parallel", "parallel", "parallel",
                                    "arbitrary")),
        interpret=interpret,
    )(x, w)

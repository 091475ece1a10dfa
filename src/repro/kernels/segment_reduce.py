"""Pallas segmented reduction by segment ids.

Backs the query engine's ``hash_agg`` in the jit backend: values arrive
in any row order, each row carrying its group id, and the kernel reduces
every segment to one output slot. Membership is a one-hot compare, so
rows of one segment need not be adjacent.

The grid is ``(segment blocks, row blocks)``. Per step a ``(C, block_n)``
slab of value columns is expanded against a ``(block_n, seg_block)``
one-hot membership matrix; ``sum``/``count`` reduce each column with a
PAIRWISE binary tree over the masked ``(bn, S)`` contributions (block
sizes are powers of two, so the tree halves cleanly), and ``min``/``max``
use masked VPU reductions — both accumulated into the segment block's
output across the row steps (sequential minor-most grid dimension, as in
``moe_gmm``). Rows padded to the block size carry segment id ``-1`` and
match no column.

The pairwise tree is what closes the float-parity gap with the float64
numpy backend: a sequential (or matmul-K-loop) float32 accumulation over
``k`` same-sign values loses ``O(k * eps)`` relative precision — ~1e-4 at
TPC fragment sizes — while the tree's error is ``O(log2(k) * eps)``,
~7e-7 even at million-row blocks. Block sums then accumulate across row
blocks with Kahan compensation, which matters on the TPU, where VMEM
bounds a block to a few thousand rows and a 1.5M-row fragment takes
hundreds of them. That is what lets the engine promise aggregate parity
at rtol=1e-6 (see ``docs/BACKENDS.md``) and run the jit backend as the
default. Interpret mode gives bit-accurate execution on CPU; on TPU the
same body compiles to Mosaic. Interpret mode executes one eager dispatch
per grid step, so on CPU the block covers the whole array in one step
up to an element budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu

_INIT = {"sum": 0.0, "count": 0.0, "min": jnp.inf, "max": -jnp.inf}

# Interpret mode: one eager dispatch per grid step, so blocks are as large
# as an element budget allows (block_n x s_pad <= ~64 MiB float32).
_INTERPRET_MAX_BLOCK = 1 << 20
_ONEHOT_ELEM_BUDGET = 1 << 24
# Mosaic: the (block_n, seg_block) one-hot and its pairwise tree live in
# VMEM. 2 MiB of float32 per block keeps the kernel well inside the
# chip's scoped VMEM (4096 x 128 at <= 128 groups, 512 x 1024 above).
_TPU_ONEHOT_ELEMS = 1 << 19
_TPU_SEG_BLOCK = 1024


def _segment_reduce_kernel(vals_ref, ids_ref, out_ref, *comp_ref, mode: str):
    row_step = pl.program_id(1)
    s_blk = out_ref.shape[-1]

    @pl.when(row_step == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _INIT[mode])
        for ref in comp_ref:
            ref[...] = jnp.zeros_like(ref)

    vals = vals_ref[...].astype(jnp.float32)           # (C, bn)
    # Segment ids relative to this grid step's segment block; ids of other
    # blocks (and the -1 padding) fall outside [0, s_blk) and match nothing.
    ids = ids_ref[0] - pl.program_id(0) * s_blk        # (bn,) int32
    seg = jax.lax.broadcasted_iota(jnp.int32, (ids.shape[0], s_blk), 1)
    onehot = ids[:, None] == seg                       # (bn, S)
    if mode in ("sum", "count"):
        # Pairwise binary tree over the masked contributions instead of a
        # one-hot matmul: same O(bn x S) flops, but the float32 rounding
        # error is O(log2 bn) instead of the matmul K-loop's O(bn) — the
        # accuracy that backs the rtol=1e-6 aggregate-parity contract.
        # bn is a power of two (enforced by the caller), so the tree
        # halves cleanly; C is static and small.
        if mode == "count":
            vals = jnp.ones_like(vals)
        comp = comp_ref[0]
        for c in range(vals.shape[0]):
            t = jnp.where(onehot, vals[c][:, None], 0.0)   # (bn, S)
            m = t.shape[0]
            while m > 1:
                m //= 2
                t = t[:m] + t[m:2 * m]
            # Block sums accumulate across row blocks with Kahan
            # compensation, so hundreds of blocks add O(eps), not
            # O(blocks x eps), to the tree's error.
            y = t[0] - comp[c]
            acc = out_ref[c] + y
            comp[c] = (acc - out_ref[c]) - y
            out_ref[c] = acc
    elif mode in ("min", "max"):
        combine = jnp.minimum if mode == "min" else jnp.maximum
        sentinel = _INIT[mode]
        for c in range(vals.shape[0]):                 # C is static, small
            masked = jnp.where(onehot, vals[c][:, None], sentinel)
            red = masked.min(axis=0) if mode == "min" else masked.max(axis=0)
            out_ref[c] = combine(out_ref[c], red)
    else:
        raise ValueError(f"unknown reduction mode {mode!r}")


def _tiling(n: int, num_segments: int, interpret: bool) -> tuple[int, int]:
    """``(block_n, seg_block)`` for ``n`` rows and ``num_segments`` slots.

    Interpret mode takes every segment in one block and as many rows as
    the element budget allows (fewer eager interpreter steps). Mosaic
    takes 128-lane segment blocks of at most ``_TPU_SEG_BLOCK`` and rows up
    to the VMEM budget. ``block_n`` is a power of two, so the in-kernel
    pairwise tree halves cleanly (rounded down: rounding up could double
    the one-hot memory)."""
    if interpret:
        seg_block = max(8, num_segments)
        block_n = min(_INTERPRET_MAX_BLOCK, _ONEHOT_ELEM_BUDGET // seg_block)
    else:
        seg_block = min(_TPU_SEG_BLOCK, -(-num_segments // 128) * 128)
        block_n = _TPU_ONEHOT_ELEMS // seg_block
    bn = max(128, min(block_n, -(-n // 128) * 128))
    return 1 << (bn.bit_length() - 1), seg_block


@functools.partial(jax.jit, static_argnames=("num_segments", "mode",
                                             "block_n", "seg_block",
                                             "interpret"))
def _segment_reduce_2d(vals, seg_ids, *, num_segments: int, mode: str,
                       block_n: int, seg_block: int, interpret: bool):
    c, n = vals.shape
    s_pad = -(-num_segments // seg_block) * seg_block
    n_pad = -(-max(n, 1) // block_n) * block_n
    vals = jnp.pad(vals, ((0, 0), (0, n_pad - n)))
    seg_ids = jnp.pad(seg_ids, (0, n_pad - n), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_segment_reduce_kernel, mode=mode),
        grid=(s_pad // seg_block, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((c, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((c, seg_block), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((c, s_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, seg_block), jnp.float32)]
        if mode in ("sum", "count") else [],
        compiler_params=tpu.params(("parallel", "arbitrary")),
        interpret=interpret,
    )(vals, seg_ids[None, :])
    return out[:, :num_segments]


def segment_reduce(vals, seg_ids, *, num_segments: int, mode: str = "sum",
                   interpret: bool = False):
    """Reduce ``vals`` into ``num_segments`` slots by ``seg_ids`` (n,).

    ``vals`` is ``(n,)`` for one column or ``(C, n)`` for a stack of
    columns reduced together (one kernel launch for all of them). Segment
    ids must be in ``[0, num_segments)``; rows with id ``-1`` are ignored.
    Returns float32 ``(num_segments,)`` / ``(C, num_segments)``.
    ``mode``: sum | count | min | max.
    """
    vals = jnp.asarray(vals, jnp.float32)
    seg_ids = jnp.asarray(seg_ids, jnp.int32).ravel()
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[None, :]
    block_n, seg_block = _tiling(vals.shape[1], num_segments, interpret)
    out = _segment_reduce_2d(vals, seg_ids, num_segments=num_segments,
                             mode=mode, block_n=block_n,
                             seg_block=seg_block, interpret=interpret)
    return out[0] if squeeze else out


def segment_reduce_np(vals: np.ndarray, seg_ids: np.ndarray,
                      num_segments: int, mode: str = "sum") -> np.ndarray:
    """Pure-numpy oracle for tests."""
    out = np.full(num_segments, _INIT[mode], dtype=np.float64)
    if mode in ("sum", "count"):
        w = np.ones_like(vals) if mode == "count" else vals
        np.add.at(out, seg_ids, w)
    elif mode == "min":
        np.minimum.at(out, seg_ids, vals)
    else:
        np.maximum.at(out, seg_ids, vals)
    return out


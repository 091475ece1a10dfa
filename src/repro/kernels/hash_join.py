"""Sorted probe for the query engine's compiled equi-join.

Backs ``hash_join`` in the jit backend: the build side arrives sorted by
key (the compiler argsorts it host-side, as the interpreted join does) and
every probe key is located with a bucket-accelerated lower-bound search:

* A radix bucket table over the high key bits (``prepare_buckets``, built
  host-side with one 64K-entry ``np.searchsorted``) narrows each probe to
  a small slice of the build array, so the binary search needs only
  ``ceil(log2(max bucket population)) + 1`` steps — ~4 for uniform TPC
  keys instead of ``log2(build rows)`` (~21 at a TPC-H SF1 fragment).
  Each step is one gather + compare over all probe keys.
* The probe emits, per probe row, the matching build position and a
  match flag. The caller gathers payload columns with the positions —
  either inside the same ``jax.jit`` trace (derived columns) or in numpy
  (pass-through columns keep their original dtype).
* ``sorted_probe_range`` is the duplicate-key variant: two bucketed
  searches (lower bound and upper bound) emit the full ``[lo, hi)`` run
  of matching build positions, whose length is the match multiplicity.
  The compiled join's counts/prefix-sum expansion is built on it
  (``engine.compile._FusedTail``).

The search is plain ``jax.numpy``, traced into the caller's ``jax.jit``
function, and is the same code on every platform. It is not a Pallas
kernel: its steps are gathers at data-dependent positions anywhere in
the build array, and Mosaic lowers only 2D gathers within a block
(``NotImplementedError: Only 2D gather is supported`` for the 1D form),
while a whole 1.5M-row build side does not fit the chip's scoped VMEM
(``docs/BACKENDS.md``). XLA lowers these gathers on the TPU directly.
Rows padded past the probe length are probed like any other row; callers
mask them out with the match flag plus their own validity mask.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Bucket table resolution: 2**16 buckets cover any int32 key span with a
# <=256 KiB starts table (fits VMEM alongside the build block).
NB_BITS = 16
NB = 1 << NB_BITS

_INT32_MAX = np.iinfo(np.int32).max

# Static search depths (rounded up) so data-dependent bucket populations
# map onto a handful of kernel specializations instead of one per depth.
_DEPTH_STEPS = (4, 8, 12, 16, 20, 24, 32)


@functools.lru_cache(maxsize=None)
def _round_depth(iters: int) -> int:
    for d in _DEPTH_STEPS:
        if iters <= d:
            return d
    return _DEPTH_STEPS[-1]


def prepare_buckets(build_sorted: np.ndarray) -> tuple[np.ndarray,
                                                       np.ndarray, int]:
    """Host-side probe acceleration structure for a sorted int32 key array.

    Returns ``(scalars, starts, iters)``: ``scalars = [bias, shift]``
    (bucket of key k is ``(k - bias) >> shift``), ``starts`` the NB+1
    bucket boundary positions, and ``iters`` the static in-kernel binary
    search depth covering the most populated bucket.
    """
    bs = np.ascontiguousarray(build_sorted, dtype=np.int32)
    s = len(bs)
    if s == 0:
        starts = np.zeros(NB + 1, np.int32)
        return np.asarray([0, 0], np.int32), starts, _DEPTH_STEPS[0]
    bias = int(bs[0])
    span = int(bs[-1]) - bias + 1        # may exceed int32 (full key span)
    shift = max(0, span.bit_length() - NB_BITS)
    bounds = bias + (np.arange(1, NB, dtype=np.int64) << shift)
    starts = np.empty(NB + 1, np.int32)
    starts[0], starts[NB] = 0, s
    # int64 bounds compare exactly against the int32 keys (no clipping:
    # a bound past INT32_MAX correctly maps its bucket start to s).
    starts[1:NB] = np.searchsorted(bs, bounds)
    max_bucket = int(np.max(np.diff(starts)))
    iters = max(1, math.ceil(math.log2(max_bucket))) + 1 if max_bucket > 1 \
        else 1
    return np.asarray([bias, shift], np.int32), starts, _round_depth(iters)


def _bucket_bounds(scalars, starts, keys):
    """Per probe key, the ``[lo, hi)`` slice of the sorted build side that
    its bucket covers."""
    bias, shift = scalars[0], scalars[1]
    # The build key span can exceed int31, so the int32 difference may
    # wrap; reinterpreting it as uint32 recovers the true offset for any
    # key >= bias (two's complement), and keys below bias wrap to huge
    # offsets that clip into the last bucket, where the equality check
    # cannot match (no build key is smaller than bias).
    diff = (keys - bias).astype(jnp.uint32)
    bucket = jnp.minimum(diff >> shift.astype(jnp.uint32),
                         jnp.uint32(NB - 1)).astype(jnp.int32)
    return starts[bucket], starts[bucket + 1]


def _bound(build, keys, lo, hi, iters: int, upper: bool):
    """Static-depth binary search inside ``[lo, hi)``: the first position
    whose key is not below (``upper``: not at or below) the probe key."""
    s_pad = build.shape[0]
    for _ in range(iters):            # one gather + compare per step
        active = lo < hi
        mid = (lo + hi) >> 1
        b = build[jnp.minimum(mid, s_pad - 1)]
        go = ((b <= keys) if upper else (b < keys)) & active
        lo = jnp.where(go, mid + 1, lo)
        hi = jnp.where(active & ~go, mid, hi)
    return lo


@functools.partial(jax.jit, static_argnames=("iters",))
def _sorted_probe_call(scalars, starts, build_sorted, keys, *, iters: int):
    b_lo, b_hi = _bucket_bounds(scalars, starts, keys)
    lo = _bound(build_sorted, keys, b_lo, b_hi, iters, upper=False)
    pos = jnp.minimum(lo, build_sorted.shape[0] - 1)
    # starts[NB] is the true (unpadded) build length.
    return pos, (build_sorted[pos] == keys) & (lo < starts[NB])


@functools.partial(jax.jit, static_argnames=("iters",))
def _sorted_probe_range_call(scalars, starts, build_sorted, keys, *,
                             iters: int):
    b_lo, b_hi = _bucket_bounds(scalars, starts, keys)
    lo = _bound(build_sorted, keys, b_lo, b_hi, iters, upper=False)
    hi = _bound(build_sorted, keys, b_lo, b_hi, iters, upper=True)
    pos = jnp.minimum(lo, build_sorted.shape[0] - 1)
    return lo, hi, (build_sorted[pos] == keys) & (lo < starts[NB])


def sorted_probe_range(build_sorted, keys, *, scalars=None, starts=None,
                       iters: int | None = None):
    """Range probe of ``keys`` (n,) into sorted ``build_sorted`` (s,).

    Returns ``(lo, hi, match)``: ``[lo[i], hi[i])`` is the contiguous run
    of build positions whose key equals ``keys[i]`` (``hi - lo`` is the
    duplicate multiplicity, 0 when absent) and ``match[i]`` whether the
    key exists. Backs the compiled duplicate-key join expansion; see
    ``sorted_probe`` for the int32 contract and bucket-structure reuse.
    """
    build_sorted = np.asarray(build_sorted) if scalars is None else \
        build_sorted
    if scalars is None:
        scalars, starts, iters = prepare_buckets(build_sorted)
    return _sorted_probe_range_call(jnp.asarray(scalars, jnp.int32),
                                    jnp.asarray(starts, jnp.int32),
                                    jnp.asarray(build_sorted, jnp.int32),
                                    jnp.asarray(keys, jnp.int32),
                                    iters=iters)


def sorted_probe_range_np(build_sorted: np.ndarray, keys: np.ndarray
                          ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pure-numpy oracle for ``sorted_probe_range``."""
    lo = np.searchsorted(build_sorted, keys, side="left")
    hi = np.searchsorted(build_sorted, keys, side="right")
    return lo.astype(np.int32), hi.astype(np.int32), hi > lo


def sorted_probe(build_sorted, keys, *, scalars=None, starts=None,
                 iters: int | None = None):
    """Lower-bound probe of ``keys`` (n,) into sorted ``build_sorted`` (s,).

    Returns ``(pos, match)``: ``pos[i]`` is the first build position whose
    key equals ``keys[i]`` (clipped into range when there is no match) and
    ``match[i]`` whether the key exists. Both sides must be int32 (the
    engine guards the int64->int32 narrowing before calling). The bucket
    structure may be passed in (``prepare_buckets``) to amortize it across
    traces; it is built on the fly otherwise.
    """
    build_sorted = np.asarray(build_sorted) if scalars is None else \
        build_sorted
    if scalars is None:
        scalars, starts, iters = prepare_buckets(build_sorted)
    return _sorted_probe_call(jnp.asarray(scalars, jnp.int32),
                              jnp.asarray(starts, jnp.int32),
                              jnp.asarray(build_sorted, jnp.int32),
                              jnp.asarray(keys, jnp.int32),
                              iters=iters)


def sorted_probe_np(build_sorted: np.ndarray, keys: np.ndarray
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Pure-numpy oracle for tests."""
    s = len(build_sorted)
    lo = np.searchsorted(build_sorted, keys)
    pos = np.minimum(lo, max(s - 1, 0))
    match = (lo < s) & (build_sorted[pos] == keys) if s else \
        np.zeros(len(keys), bool)
    return pos.astype(np.int32), match

"""Seeded draws: every random choice of a run comes from ``rng(seed,
stream)``, one independent stream per use (window, warm-up, data), each a
pure function of the seed, so the same seed gives the same inputs.
"""
from __future__ import annotations

import numpy as np

STREAMS = ("window", "warmup", "data")


def rng(seed: int, stream: str, *sub: int) -> np.random.Generator:
    """The numpy generator of one named stream of ``seed`` (and of a
    numbered sub-stream, such as one table of the data)."""
    return np.random.default_rng([seed % (1 << 63), STREAMS.index(stream),
                                  *sub])


def draw_param(spec: dict, r: np.random.Generator):
    """``{"integers": [lo, hi]}`` (inclusive, optionally times ``scale``)
    drawn from ``r``, or ``{"value": v}``."""
    if "value" in spec:
        return spec["value"]
    lo, hi = spec["integers"]
    k = int(r.integers(lo, hi + 1))
    scale = spec.get("scale")
    return k if scale is None else round(k * scale, 10)

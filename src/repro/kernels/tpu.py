"""Where the Pallas kernels run, and what Mosaic is told about them.

Every kernel in this package takes ``interpret=``; callers pass
``interpret()``. On a TPU backend the kernels compile to Mosaic; on any
other backend they execute through the Pallas interpreter (bit-accurate
kernel-body semantics on CPU, which is how the tests run them). Nothing
here decides whether a process *should* be on the TPU — entry points
that need the chip check the platform themselves (``chip_smoke.py``).
"""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def interpret() -> bool:
    """True off the TPU: kernels run through the Pallas interpreter."""
    return jax.default_backend() != "tpu"


def params(dimension_semantics) -> pltpu.CompilerParams:
    """Mosaic compiler parameters for a kernel's grid."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)

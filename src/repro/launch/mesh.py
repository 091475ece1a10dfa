"""Mesh construction: every mesh in the program is built here.

Axes are ``AxisType.Auto``: the step functions place arrays with
``NamedSharding``s and ``with_sharding_constraint`` and let GSPMD
propagate the rest. (``jax.make_mesh`` alone builds Explicit axes, under
which sharding becomes part of every array's type and ops such as the KV
cache's ``dynamic_update_slice`` reject operands whose shardings differ.)

Defined as functions (never module-level constants) so importing this
module does not touch JAX device state — the dry-run must set XLA_FLAGS
before the first JAX initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(data: int = 1, model: int = 1, pod: int = 0, devices=None):
    """A ``(data, model)`` mesh, or ``(pod, data, model)`` when ``pod``.

    ``devices`` defaults to all of them; pass a prefix of ``jax.devices()``
    for a mesh smaller than the host (e.g. a one-device reference beside a
    sharded one)."""
    shape = (pod, data, model) if pod else (data, model)
    names = ("pod", "data", "model") if pod else ("data", "model")
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod mesh (data, model); 2x16x16 with a DCN 'pod' axis."""
    return make_mesh(16, 16, pod=2 if multi_pod else 0)

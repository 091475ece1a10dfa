"""Error-feedback int8 gradient compression for cross-pod (DCN) reduction.

The multi-pod mesh reduces gradients over the 'pod' axis through DCN, which
is an order of magnitude slower than ICI. 1-bit/8-bit Adam-style
compression (Seide et al. 2014; Tang et al., arXiv:2102.02888) cuts those
bytes 4x vs fp32 / 2x vs bf16, with the quantization error fed back into
the next step so convergence is preserved.

``compressed_psum`` runs the quantize -> psum -> dequantize pipeline inside
``jax.shard_map`` (manual over the reduction axis only), so the
collective payload really is int8 on the wire, visible in the dry-run
HLO.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-tensor symmetric int8; returns (q, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def ef_compress(grad: jax.Array, error: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Error-feedback compression of one tensor: returns
    (q, scale, new_error)."""
    target = grad.astype(jnp.float32) + error
    q, scale = quantize_int8(target)
    new_error = target - dequantize_int8(q, scale)
    return q, scale, new_error


def init_error_state(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def compressed_psum(partials, error_state, mesh, axis: str = "pod"):
    """Mean-reduce per-``axis`` partial gradients with int8 payloads.

    ``partials`` leaves carry a leading dim of size n_pods (stacked per-pod
    partial sums, sharded over ``axis``); ``error_state`` matches. Returns
    (fp32 mean over pods, new error state).

    Exactness: a shared scale is agreed via pmax *before* quantization, so
    the int32-accumulated sum dequantizes exactly; only the per-pod
    quantization error remains, and that is fed back next step.

    Wire payload per tensor: 1 byte/element (+ a scalar), vs 4 for fp32 —
    a 4x DCN reduction.
    """
    if axis not in mesh.axis_names:
        return (jax.tree.map(lambda g: g[0].astype(jnp.float32), partials),
                error_state)
    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def one(g, e):
        def local(gl, el):
            gl = gl[0].astype(jnp.float32)
            el = el[0]
            target = gl + el
            amax = jax.lax.pmax(jnp.max(jnp.abs(target)), axis)
            scale = jnp.maximum(amax, 1e-12) / 127.0
            q = jnp.clip(jnp.round(target / scale), -127, 127)
            # int8 on the wire (an int8 psum would overflow; gather then
            # accumulate locally in int32)
            gathered = jax.lax.all_gather(q.astype(jnp.int8), axis)
            total = jnp.sum(gathered.astype(jnp.int32), axis=0)
            out = total.astype(jnp.float32) * scale / n
            new_e = target - q * scale
            return out, new_e[None]

        in_spec = P(axis, *([None] * (g.ndim - 1)))
        out_spec = P(*([None] * (g.ndim - 1)))
        return jax.shard_map(local, mesh=mesh,
                             in_specs=(in_spec, in_spec),
                             out_specs=(out_spec, in_spec),
                             check_vma=False)(g, e)

    flat_g, treedef = jax.tree.flatten(partials)
    flat_e = treedef.flatten_up_to(error_state)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (treedef.unflatten([o[0] for o in outs]),
            treedef.unflatten([o[1] for o in outs]))

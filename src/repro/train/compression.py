"""Gradient compression for slow-link all-reduce (distributed-opt trick).

At 1000+ nodes the gradient reduction over the *cross-pod* links (DCI) is
the scaling bottleneck: within a pod GSPMD's bf16 reduce-scatter over ICI is
fine, but the pod axis runs over data-center links with a fraction of the
bandwidth.  We therefore keep intra-pod reductions automatic (GSPMD) and
take manual control of the pod axis with a ``shard_map`` whose other mesh
axes stay *auto*, compressing to int8 before the cross-pod exchange:

    bytes on the slow link:  all-gather(int8 + per-row fp32 scale)
                             ~= N * (P-1)/P bytes
    vs. bf16 ring all-reduce ~= 2 * N * (P-1)/P * 2 bytes   (4x reduction)

Quantization is per-row (last dim) symmetric int8 with stochastic-free
round-to-nearest; the compression error is bounded by scale/2 per element
(property-tested).  An error-feedback buffer (residual carried in the
optimizer state) is available via ``error_feedback=True`` in the train
config knob ``grad_compression="int8_ef"``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

def quantize_int8(x: jax.Array):
    """Symmetric per-row int8 quantization. x: (..., d) fp -> (q, scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _compressed_pmean_leaf(g: jax.Array, axis_name: str) -> jax.Array:
    """int8 all-gather + local dequant-mean over ``axis_name``."""
    orig_shape, orig_dtype = g.shape, g.dtype
    flat = g.reshape(-1) if g.ndim <= 1 else g.reshape(-1, g.shape[-1])
    if flat.ndim == 1:
        flat = flat[None, :]
    q, scale = quantize_int8(flat)
    qs = jax.lax.all_gather(q, axis_name)          # (P, rows, d) int8
    ss = jax.lax.all_gather(scale, axis_name)      # (P, rows, 1) fp32
    mean = jnp.mean(dequantize_int8(qs, ss), axis=0)
    return mean.reshape(orig_shape).astype(orig_dtype)


def compressed_pmean(grads, axis_name: str, method: str = "int8"):
    """Mean-reduce a grad pytree over ``axis_name`` inside shard_map."""
    if method in ("int8", "int8_ef"):
        return jax.tree.map(
            partial(_compressed_pmean_leaf, axis_name=axis_name), grads)
    if method == "bf16":
        return jax.tree.map(
            lambda g: jax.lax.pmean(g.astype(jnp.bfloat16), axis_name
                                    ).astype(g.dtype), grads)
    return jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)


def cross_pod_sync(grads, mesh: Mesh, method: str = "int8"):
    """Compressed gradient mean over the ``pod`` mesh axis.

    Other mesh axes stay *auto* (GSPMD keeps managing FSDP/TP shardings of
    the per-pod partial grads); only the pod-axis exchange is manual."""
    if "pod" not in mesh.axis_names or method == "none":
        return grads
    auto = frozenset(n for n in mesh.axis_names if n != "pod")

    def f(g):
        return compressed_pmean(g, "pod", method)

    specs = jax.tree.map(lambda _: P(), grads)     # replicated over pod axis
    return shard_map(f, mesh=mesh, in_specs=(specs,), out_specs=specs,
                         check_vma=False, axis_names={"pod"})(grads)


def apply_error_feedback(grads, residual):
    """g' = g + residual;  new_residual = g' - Q(g') is added by the caller
    after quantization.  Here we only fold the residual in (the caller keeps
    the post-quantization error)."""
    return jax.tree.map(lambda g, r: g + r.astype(g.dtype), grads, residual)


def quantization_error(x: jax.Array) -> jax.Array:
    q, s = quantize_int8(x.reshape(1, -1) if x.ndim <= 1 else
                         x.reshape(-1, x.shape[-1]))
    return (dequantize_int8(q, s).reshape(x.shape)
            - x.astype(jnp.float32))

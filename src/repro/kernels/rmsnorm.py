"""Fused RMSNorm — Pallas TPU kernel.

Bandwidth-bound fusion: one HBM read of x, one write of y, with the fp32
mean-square reduction and the scale multiply fused in VMEM (XLA emits this
as 2-3 kernels with an fp32 intermediate when the surrounding dtypes are
bf16).  Rows are tiled (bn, d) so the working set stays in VMEM; d is kept
whole because the reduction runs over it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BLOCK_BUDGET = 8 << 20


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # (bn, d)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * scale_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "bn", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, bn: int = 256,
            interpret: bool = False):
    """x: (..., d); scale: (d,).  Fused RMSNorm over the last dim."""
    orig_shape = x.shape
    d = x.shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    # the in and out blocks, double-buffered, must fit the 16 MiB of scoped
    # VMEM a TPU kernel gets: fp32 rows of d=4096 allow 128 of them
    bn = min(bn, n, max(8, _VMEM_BLOCK_BUDGET // (4 * d * x.dtype.itemsize)))
    while n % bn != 0:                 # ragged fallback for odd row counts
        bn -= 1
    ce = cost_estimate(x.shape, x.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="rmsnorm",
        cost_estimate=pl.CostEstimate(flops=int(ce["flops"]),
                                      transcendentals=n,
                                      bytes_accessed=int(ce["bytes"])),
    )(xf, scale)
    return out.reshape(orig_shape)


def cost_estimate(x_shape, itemsize: int) -> dict:
    """Analytic per-call ``{flops, bytes}`` for one rmsnorm call: declared
    to the compiler as the kernel's ``pl.CostEstimate`` (what the HLO walk
    reads back from the compiled kernel) and used as is in interpret mode.
    Bandwidth-bound by design: ~4
    VPU ops per element (square, mean-accumulate, rsqrt-scale, gain)
    against one read + one write of x plus the scale vector.
    """
    numel = 1
    for dim in x_shape:
        numel *= int(dim)
    d = int(x_shape[-1])
    return {"flops": 4.0 * numel,
            "bytes": float((2 * numel + d) * itemsize)}

"""Mamba2 SSD chunk scan — Pallas TPU kernel.

The SSD recurrence is the throughput hot-spot of the SSM/hybrid archs
(zamba2 long-context).  TPU mapping: the chunk dimension is a *sequential*
grid axis carrying the (P, N) state in VMEM scratch; per chunk, the three
contractions (intra-chunk C B^T, state write B^T x, state read C S) are MXU
matmuls on (C, N)x(C, P) tiles, and the decay weights come from a cumulative
log-sum built in-register.  This keeps the state resident in VMEM for the
whole sequence — the chunked-scan analogue of flash attention's accumulator.

Layout: one (batch, head) pair per grid row; inputs pre-transposed to
(B, H, L, ...) by ``ops.ssd_chunked_kernel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, o_ref, state_ref, *,
                chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)                   # (C, P)
    a = a_ref[0, 0].astype(jnp.float32)                   # (1, C)
    b = b_ref[0, 0].astype(jnp.float32)                   # (C, N)
    c = c_ref[0, 0].astype(jnp.float32)                   # (C, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = ii >= jj
    # inclusive cumulative log decay as a column: a_cs[i] = sum_{j<=i} a[j]
    a_cs = jnp.sum(jnp.where(lower, jnp.broadcast_to(a, (chunk, chunk)),
                             0.0), axis=1, keepdims=True)  # (C, 1)
    a_total = jnp.sum(a)

    # intra-chunk: pair[i, j] = exp(a_cs_i - a_cs_j) for i >= j else 0
    cs = jnp.broadcast_to(a_cs, (chunk, chunk))
    pair = jnp.where(lower, jnp.exp(cs - cs.T), 0.0)      # (C, C)

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C, C)
    y_diag = jax.lax.dot_general(cb * pair, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: y_off = (C . S_prev) * exp(a_cs)
    s_prev = state_ref[...]                               # (N, P)
    y_off = jax.lax.dot_general(c, s_prev, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_off = y_off * jnp.exp(a_cs)

    o_ref[0, 0] = (y_diag + y_off).astype(o_ref.dtype)

    # state update: S_new = exp(a_total) S_prev + B^T (x * decay_to_end)
    xw = x * jnp.exp(a_total - a_cs)                      # decay <= 1
    s_chunk = jax.lax.dot_general(b, xw, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = s_prev * jnp.exp(a_total) + s_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, a, b, c, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD scan.

    x: (B, H, L, P) — dt-premultiplied inputs;
    a: (B, H, L)    — per-step log decays (dt * A, <= 0);
    b/c: (B, H, L, N) — input/output projections (groups pre-broadcast).
    Returns y: (B, H, L, P).
    """
    bsz, h, l, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    ce = cost_estimate(x.shape, n, x.dtype.itemsize, chunk=chunk)
    # ``a`` rides as a (1, chunk) row block: the TPU lowering wants the last
    # two block dims tiled (8, 128) or whole, which a (chunk,) block is not
    return pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
        cost_estimate=pl.CostEstimate(flops=int(ce["flops"]),
                                      transcendentals=0,
                                      bytes_accessed=int(ce["bytes"])),
    )(x, a[:, :, None, :], b, c)


def cost_estimate(x_shape, state_n: int, itemsize: int, *,
                  chunk: int = 128) -> dict:
    """Analytic per-call ``{flops, bytes}`` for one ssd_scan call: declared
    to the compiler as the kernel's ``pl.CostEstimate`` (what the HLO walk
    reads back from the compiled kernel) and used as is in interpret mode.

    Per chunk of C steps the kernel runs four contractions: the
    within-chunk attention pair (c@b^T then p@x, 2*C^2*(N+P)) and the
    inter-chunk state pair (c@S and b^T@xw, 2*C*N*P each).  Bytes: one
    read of x/a/b/c + one write of y.
    """
    bsz, h, l, p = x_shape
    n = state_n
    c = min(chunk, l)
    nc = l // max(c, 1)
    per_chunk = 2.0 * c * c * (n + p) + 4.0 * c * n * p
    flops = float(bsz * h * nc * per_chunk)
    elems = bsz * h * l * (2 * p + 2 * n + 1)           # x + y + b + c + a
    return {"flops": flops, "bytes": float(elems * itemsize)}

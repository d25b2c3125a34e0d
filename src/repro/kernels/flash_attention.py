"""Flash attention — Pallas TPU kernel (blocked online-softmax).

TPU adaptation notes (DESIGN.md §2/§7): the CUDA flash algorithm keys off
shared-memory tiles + warp shuffles; on TPU the same insight (never
materialize the S^2 score matrix in HBM) maps to VMEM-resident (bq, bk)
tiles feeding the MXU, with the online-softmax running state (m, l, acc)
held in VMEM scratch across the sequential kv-block grid dimension.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks) — the kv dimension is
marked "arbitrary" (sequential) so scratch carries across it.  GQA is
handled in the BlockSpec index maps (kv tensors index head ``h // group``),
causal + sliding-window masking by block-local position arithmetic, and
fully-masked blocks are skipped with ``pl.when`` (the block-skipping a
flash kernel gets for free and XLA's dense masked attention does not).

Block sizes default to 128 (MXU-aligned); the head dim is kept whole in
VMEM: (128 x 128) fp32 tiles => ~200 KB of VMEM scratch, far under the
~16 MB/core budget, leaving room for double buffering.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int, bq: int, bk: int,
                 seq_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    # block-level skip: causal => no kv block strictly above the diagonal;
    # sliding window => no kv block entirely left of the window
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                      # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = k_pos < seq_len
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KV, S, D); returns (B, H, S, D).

    H must be a multiple of KV (GQA).  S must divide by the block sizes
    (callers pad; the assigned shapes are powers of two).
    """
    b, h, s, d = q.shape
    kv = k.shape[1]
    assert h % kv == 0, (h, kv)
    g = h // kv
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    grid = (b, h, s // bq, s // bk)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_attn_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, seq_len=s)
    ce = cost_estimate(q.shape, kv, q.dtype.itemsize, causal=causal,
                       window=window, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
        cost_estimate=pl.CostEstimate(flops=int(ce["flops"]),
                                      transcendentals=0,
                                      bytes_accessed=int(ce["bytes"])),
    )(q, k, v)


def cost_estimate(q_shape, kv_heads: int, itemsize: int, *,
                  causal: bool = True, window: int = 0,
                  bk: int = 128) -> dict:
    """Analytic per-call ``{flops, bytes}`` for one flash_attention call:
    declared to the compiler as the kernel's ``pl.CostEstimate`` (what the
    HLO walk reads back from the compiled kernel) and used as is in
    interpret mode.

    FLOPs: the two MXU contractions, 2*S_q*S_kv*D each for QK^T and PV;
    causal masking skips roughly half the key blocks, a sliding window
    of w keeps ~(w+bk) keys per query.  Bytes: one read of q/k/v + one
    write of o (HBM traffic of a single-pass fused kernel).
    """
    b, h, s, d = q_shape
    frac = 1.0
    if window and window > 0:
        frac = min(1.0, (window + bk) / s)
    elif causal:
        frac = 0.5
    flops = 4.0 * b * h * s * s * d * frac
    elems = b * s * d * (2 * h + 2 * kv_heads)          # q + o + k + v
    return {"flops": flops, "bytes": float(elems * itemsize)}

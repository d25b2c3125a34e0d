"""Flash attention — Pallas TPU kernels (blocked online softmax), forward
and backward.

TPU adaptation notes (DESIGN.md §2/§7): the CUDA flash algorithm keys off
shared-memory tiles + warp shuffles; on TPU the same insight (never
materialize the S^2 score matrix in HBM) maps to VMEM-resident (bq, bk)
tiles feeding the MXU, with the online-softmax running state (m, l, acc)
held in VMEM scratch across the sequential kv-block grid dimension (l as
per-lane partial sums, so a block adds on the VPU and only the finalize
sums across lanes).

GQA: the G query heads that share a KV head are one query block, so the
grid runs over KV heads and each K/V block is fetched once per group.
Queries are viewed as (B, KV, G, S, D), a free reshape of (B, H, S, D).

Grids (the last dimension is sequential, so scratch carries across it):

* forward and dQ: (B, KV, S/bq, S/bk), accumulating over key blocks;
* dK/dV: (B, KV, S/bk, S/bq), accumulating over query blocks and over the
  G query heads of the KV head.

A block with no visible (query, key) pair is skipped (``pl.when``), and
its streamed operand's ``index_map`` is clamped to the nearest live block,
so a dead step repeats the previous block index and issues no DMA.  Blocks
wholly inside the visible region skip the mask arithmetic.

Precision: the MXU takes the inputs' dtype (bf16 on the chip) with float32
accumulation; the softmax state and the per-row logsumexp are float32, and
the probabilities are cast to v's dtype before the PV product, as the
masked XLA path casts its probabilities.  Float32 inputs stay float32.

The backward recomputes each block's probabilities from q, k and the
forward's logsumexp, with the usual ``D = rowsum(dO * O)`` term.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
LANES = 128
# block sizes for the chip: the fastest of a sweep at S=2048, D=128 on a
# v5e that also fits float32 blocks in the default scoped VMEM (PERF.md §5)
BLOCK_Q = 512
BLOCK_K = 512

_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b


def fits(seq_len: int, head_dim: int) -> bool:
    """Whether the compiled kernels take this shape: a sequence on the
    128-lane tiling (the blocks then divide it) and the head dim the blocks
    are sized for (at 256, float32 blocks overflow the scoped VMEM)."""
    return seq_len % LANES == 0 and head_dim == LANES


def _fit(block: int, s: int) -> int:
    """The largest of block, block/2, ... that divides s (at most s)."""
    b = min(block, s)
    while s % b:
        b //= 2
    return b


# -- block geometry (shared by the kernels, their index maps and costs) ------


def _live(q_start, k_start, bq, bk, causal, window):
    """Some (query, key) pair of the block is visible: q - k in [0, window)
    (causal), q - k < window (window only), anything (neither)."""
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window:
        live = _and(live, q_start - (k_start + bk - 1) <= window - 1)
    return live


def _full(q_start, k_start, bq, bk, causal, window):
    """Every (query, key) pair of the block is visible: no mask needed."""
    full = True
    if causal:
        full = k_start + bk - 1 <= q_start
    if window:
        full = _and(full, q_start + bq - 1 - k_start <= window - 1)
    return full


def _and(a, b):
    if isinstance(a, bool) and isinstance(b, bool):     # static geometry
        return a and b
    if a is True:
        return b
    return jnp.logical_and(a, b)


def _kv_range(iq, bq, bk, nk, causal, window):
    """Live key blocks [lo, hi] of query block iq."""
    lo, hi = 0, nk - 1
    if causal:
        hi = (iq * bq + bq - 1) // bk
    if window:
        lo = jnp.maximum(iq * bq - window + 1, 0) // bk
    return lo, hi


def _q_range(ik, bq, bk, nq, causal, window):
    """Live query blocks [lo, hi] of key block ik."""
    lo, hi = 0, nq - 1
    if causal:
        lo = (ik * bk) // bq
    if window:
        hi = jnp.minimum((ik * bk + bk - 1 + window - 1) // bq, nq - 1)
    return lo, hi


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


def _visible(q_start, k_start, shape, causal, window, *, transposed=False):
    """Mask of visible pairs for a (rows, cols) score tile; rows are
    queries, or keys when ``transposed``."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if transposed:
        q_pos, k_pos = q_start + c, k_start + r
    else:
        q_pos, k_pos = q_start + r, k_start + c
    ok = None
    if causal:
        ok = k_pos <= q_pos
    if window:
        w = k_pos > q_pos - window
        ok = w if ok is None else jnp.logical_and(ok, w)
    return ok


def _masked_steps(live, full, step):
    """Run ``step(masked)`` on a live block: unmasked where every pair is
    visible, masked where only some are."""
    if full is True:                    # neither causal nor windowed
        step(False)
        return
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(lambda: step(True))
    pl.when(full)(lambda: step(False))


def _lane_sums(x):
    """(n, k*LANES) -> (n, LANES) partial row sums: adds the lane-aligned
    column slices on the VPU and leaves the cross-lane sum to the finalize
    (a block narrower than the lanes puts its whole sum in lane 0)."""
    n, w = x.shape
    if w % LANES:
        return jnp.concatenate([jnp.sum(x, axis=-1, keepdims=True),
                                jnp.zeros((n, LANES - 1), x.dtype)], axis=1)
    out = x[:, :LANES]
    for i in range(1, w // LANES):
        out = out + x[:, i * LANES:(i + 1) * LANES]
    return out


def _col(row):
    """(1, n) -> (n, 1): a lane vector to a sublane vector."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _row(col):
    """(n, 1) -> (1, n)."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1, :]


# -- forward ------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, window, bq, bk, g):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start, k_start = iq * bq, ik * bk

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        ok = _visible(q_start, k_start, (bq, bk), causal, window) \
            if masked else None
        for h in range(g):
            s = jax.lax.dot_general(q_ref[h], k, _NT,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h]                               # (bq, LANES)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + _lane_sums(p)
            m_ref[h] = m_new
            pv = jax.lax.dot_general(p.astype(v.dtype), v, _NN,
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = alpha[:, :1] * acc_ref[h] + pv

    _masked_steps(_live(q_start, k_start, bq, bk, causal, window),
                  _full(q_start, k_start, bq, bk, causal, window), step)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        for h in range(g):
            l = jnp.sum(l_ref[h], axis=-1, keepdims=True)
            o_ref[h] = (acc_ref[h] / l).astype(o_ref.dtype)
            lse_ref[h:h + 1, :] = _row(m_ref[h][:, :1] + jnp.log(l))


# the last grid axis carries the accumulators; v5e has one core per chip
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _query_major_specs(g, bq, bk, d, nk, causal, window):
    """Specs of a (B, KV, S/bq, S/bk) grid: query-like (G, bq, D) blocks,
    K/V blocks clamped to the query block's live range, and (G, bq) row
    statistics."""
    def kv_map(b_, h_, iq, ik):
        lo, hi = _kv_range(iq, bq, bk, nk, causal, window)
        return (b_, h_, _clamp(ik, lo, hi), 0)

    return (pl.BlockSpec((None, None, g, bq, d),
                         lambda b_, h_, iq, ik: (b_, h_, 0, iq, 0)),
            pl.BlockSpec((None, None, bk, d), kv_map),
            pl.BlockSpec((None, None, g, bq),
                         lambda b_, h_, iq, ik: (b_, h_, 0, iq)))


def _fwd(q, k, v, causal, window, bq, bk, interpret):
    """q: (B, KV, G, S, D); k/v: (B, KV, S, D) -> o like q, lse (B, KV, G, S)
    float32."""
    b, kvh, g, s, d = q.shape
    nq, nk = s // bq, s // bk
    q_spec, kv_spec, lse_spec = _query_major_specs(g, bq, bk, d, nk, causal,
                                                   window)
    kernel = functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d),
                               causal=causal, window=window, bq=bq, bk=bk,
                               g=g)
    ce = cost_estimate((b, kvh * g, s, d), kvh, q.dtype.itemsize,
                       causal=causal, window=window, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(b, kvh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, kvh, g, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, LANES), jnp.float32),
                        pltpu.VMEM((g, bq, LANES), jnp.float32),
                        pltpu.VMEM((g, bq, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention",
        cost_estimate=_ce(ce),
    )(q, k, v)


# -- backward -------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, lse_col, di_col, *, scale, causal, window, bq, bk, g):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for h in range(g):
            lse_col[h] = jnp.broadcast_to(_col(lse_ref[h:h + 1, :]),
                                          (bq, LANES))
            di_col[h] = jnp.broadcast_to(_col(di_ref[h:h + 1, :]),
                                         (bq, LANES))

    q_start, k_start = iq * bq, ik * bk

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        ok = _visible(q_start, k_start, (bq, bk), causal, window) \
            if masked else None
        for h in range(g):
            s = jax.lax.dot_general(q_ref[h], k, _NT,
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            p = jnp.exp(s - lse_col[h][:, :1])
            dp = jax.lax.dot_general(do_ref[h], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - di_col[h][:, :1])
            acc_ref[h] += jax.lax.dot_general(
                ds.astype(k.dtype), k, _NN,
                preferred_element_type=jnp.float32)

    _masked_steps(_live(q_start, k_start, bq, bk, causal, window),
                  _full(q_start, k_start, bq, bk, causal, window), step)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, causal, window, bq, bk, g):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * bq, ik * bk

    def step(masked):
        k, v = k_ref[...], v_ref[...]
        ok = _visible(q_start, k_start, (bk, bq), causal, window,
                      transposed=True) if masked else None
        for h in range(g):
            q, do = q_ref[h], do_ref[h]
            st = jax.lax.dot_general(k, q, _NT,
                                     preferred_element_type=jnp.float32)
            st = st * scale                                 # (bk, bq)
            if masked:
                st = jnp.where(ok, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[h:h + 1, :])
            dv_acc[...] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - di_ref[h:h + 1, :])
            dk_acc[...] += jax.lax.dot_general(
                dst.astype(q.dtype), q, _NN,
                preferred_element_type=jnp.float32)

    _masked_steps(_live(q_start, k_start, bq, bk, causal, window),
                  _full(q_start, k_start, bq, bk, causal, window), step)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(causal, window, bq, bk, interpret, res, do):
    q, k, v, o, lse = res
    b, kvh, g, s, d = q.shape
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(d)
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    static = dict(scale=scale, causal=causal, window=window, bq=bq, bk=bk,
                  g=g)
    shape = (b, kvh * g, s, d)

    # dQ: grid over query blocks, key blocks sequential
    q_spec, kv_spec, row_spec = _query_major_specs(g, bq, bk, d, nk, causal,
                                                   window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        grid=(b, kvh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, d), jnp.float32),
                        pltpu.VMEM((g, bq, LANES), jnp.float32),
                        pltpu.VMEM((g, bq, LANES), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_dq",
        cost_estimate=_ce(cost_estimate(
            shape, kvh, q.dtype.itemsize, causal=causal, window=window,
            bq=bq, bk=bk, kernel="dq")),
    )(q, k, v, do, lse, di)

    # dK, dV: grid over key blocks, query blocks sequential
    def qo_map(b_, h_, ik, iq):
        lo, hi = _q_range(ik, bq, bk, nq, causal, window)
        return (b_, h_, 0, _clamp(iq, lo, hi), 0)

    def stat_map(b_, h_, ik, iq):
        lo, hi = _q_range(ik, bq, bk, nq, causal, window)
        return (b_, h_, 0, _clamp(iq, lo, hi))

    qo_spec = pl.BlockSpec((None, None, g, bq, d), qo_map)
    k_spec = pl.BlockSpec((None, None, bk, d),
                          lambda b_, h_, ik, iq: (b_, h_, ik, 0))
    stat_spec = pl.BlockSpec((None, None, g, bq), stat_map)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        grid=(b, kvh, nk, nq),
        in_specs=[qo_spec, k_spec, k_spec, qo_spec, stat_spec, stat_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="flash_attention_dkv",
        cost_estimate=_ce(cost_estimate(
            shape, kvh, q.dtype.itemsize, causal=causal, window=window,
            bq=bq, bk=bk, kernel="dkv")),
    )(q, k, v, do, lse, di)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _attention(q, k, v, causal, window, bq, bk, interpret):
    return _fwd(q, k, v, causal, window, bq, bk, interpret)[0]


def _attention_fwd(q, k, v, causal, window, bq, bk, interpret):
    o, lse = _fwd(q, k, v, causal, window, bq, bk, interpret)
    return o, (q, k, v, o, lse)


_attention.defvjp(_attention_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = BLOCK_Q, bk: int = BLOCK_K,
                    interpret: bool = False):
    """q: (B, H, S, D); k/v: (B, KV, S, D); returns (B, H, S, D).

    Differentiable (``jax.custom_vjp``).  H must be a multiple of KV (GQA);
    the blocks shrink by halves to divide S.
    """
    b, h, s, d = q.shape
    kvh = k.shape[1]
    assert h % kvh == 0, (h, kvh)
    bq, bk = _fit(bq, s), _fit(bk, s)
    o = _attention(q.reshape(b, kvh, h // kvh, s, d), k, v, causal, window,
                   bq, bk, interpret)
    return o.reshape(b, h, s, d)


# -- costs --------------------------------------------------------------------

# MXU products per visible score tile: QK^T and PV forward; QK^T, dO V^T
# and dS K for dQ; QK^T, P^T dO, dO V^T and dS^T Q for dK/dV
_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def _ce(c: dict) -> pl.CostEstimate:
    return pl.CostEstimate(flops=int(c["flops"]),
                           transcendentals=int(c["transcendentals"]),
                           bytes_accessed=int(c["bytes"]))


def live_blocks(s: int, bq: int, bk: int, *, causal: bool = True,
                window: int = 0) -> int:
    """Number of (query block, key block) tiles the kernels compute."""
    return sum(bool(_live(iq * bq, ik * bk, bq, bk, causal, window))
               for iq in range(s // bq) for ik in range(s // bk))


def cost_estimate(q_shape, kv_heads: int, itemsize: int, *,
                  causal: bool = True, window: int = 0, bq: int = BLOCK_Q,
                  bk: int = BLOCK_K, kernel: str = "fwd") -> dict:
    """Analytic per-call ``{flops, transcendentals, bytes}`` of one kernel
    (``fwd``, ``dq`` or ``dkv``): declared to the compiler as the kernel's
    ``pl.CostEstimate`` (what the HLO walk reads back from the compiled
    kernel) and used as is in interpret mode.

    FLOPs: 2*bq*bk*D per MXU product per computed tile (``_PRODUCTS``), over
    the tiles the block skipping leaves (the diagonal tiles are computed
    whole); one exp per computed score.  Bytes: the operands held across
    the sequential axis once, the streamed ones (K/V, or Q/dO with their
    row statistics for dK/dV) once per computed tile.
    """
    b, h, s, d = q_shape
    bq, bk = _fit(bq, s), _fit(bk, s)
    tiles = live_blocks(s, bq, bk, causal=causal, window=window)
    scores = float(b * h * tiles * bq * bk)
    q_bytes = b * h * s * d * itemsize                  # q, o, dO or dQ
    kv_bytes = b * kv_heads * s * d * itemsize          # k, v, dK or dV
    stats = b * h * s * 4                               # lse or D rows
    streamed = tiles / ((s // bq) * (s // bk))
    if kernel == "fwd":
        nbytes = 2 * q_bytes + stats + 2 * kv_bytes * (s // bq) * streamed
    elif kernel == "dq":
        nbytes = (3 * q_bytes + 2 * stats
                  + 2 * kv_bytes * (s // bq) * streamed)
    else:
        nbytes = (4 * kv_bytes
                  + (2 * q_bytes + 2 * stats) * (s // bk) * streamed)
    return {"flops": 2.0 * _PRODUCTS[kernel] * d * scores,
            "transcendentals": scores, "bytes": float(nbytes)}

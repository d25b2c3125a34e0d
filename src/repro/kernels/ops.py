"""Jit'd wrappers: model-layout adapters over the Pallas kernels.

Models store activations as (B, S, H, D); the kernels want (B, H, S, D).
These wrappers do the transposes, pick block sizes, and expose the
``interpret`` switch (CPU validation; compiled Mosaic on TPU).  They are the
only entry points the model code and the tests use.

Marker instrumentation (``repro.core.marker``): :func:`set_kernel_markers`
installs a ``MarkerSession`` and every *eager* wrapper call becomes a
``kernel:<name>`` region — synced with ``block_until_ready`` inside the
region so the wall time is the kernel's, and seeded with static per-call
flops/bytes so the region carries its own roofline operands.  A compiled
kernel's costs come from ``launch/hlo_analysis`` over its artifact (the
flops the kernel declares as its ``pl.CostEstimate``, the bytes its
operands and result move); interpret mode takes the kernels' analytic
``cost_estimate`` helpers.  Either way they are memoized per shape.  Calls
made under a jax trace (inside ``jit``) are never instrumented — a traced
wrapper body runs once at trace time, so timing it would be noise — and
uninstrumented calls pay nothing (one ``None`` check, no sync).
"""

from __future__ import annotations

import jax

import repro.kernels.flash_attention as _fa
import repro.kernels.rmsnorm as _rms
import repro.kernels.ssd as _ssd

_markers = None
_COSTS: dict = {}       # (kernel, shape/static key) -> {"flops", "bytes"}


def set_kernel_markers(session):
    """Install (or clear, with ``None``) the marker session used by the
    kernel wrappers; returns the previous session so callers can
    restore it."""
    global _markers
    prev = _markers
    _markers = session
    return prev


def _eager(*arrays) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _costs(key, lower_fn, analytic_fn, interpret: bool) -> dict:
    """Memoized per-call static costs.  Interpret-mode lowering emulates
    the kernel with callbacks (its HLO costs are meaningless), so it takes
    the analytic estimate; a compiled kernel takes the HLO walk over its
    artifact, and a walk that fails or finds no work raises."""
    c = _COSTS.get(key)
    if c is not None:
        return c
    if interpret:
        c = analytic_fn()
    else:
        from repro.launch.hlo_analysis import analyze_hlo
        per = analyze_hlo(lower_fn().compile().as_text())["per_device"]
        c = {"flops": float(per["flops"]), "bytes": float(per["bytes"])}
        if c["flops"] <= 0.0 or c["bytes"] <= 0.0:
            raise ValueError(f"HLO walk found no work in {key[0]}: {c}")
    _COSTS[key] = c
    return c


def flash_attention_bshd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = _fa.BLOCK_Q, bk: int = _fa.BLOCK_K,
                         interpret: bool = False):
    """q: (B, S, H, D); k/v: (B, S, KV, D) -> (B, S, H, D).  Differentiable:
    the kernel's backward runs under ``jax.grad``."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    m = _markers
    if m is None or not _eager(q, k, v):
        o = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                                bq=bq, bk=bk, interpret=interpret)
        return o.transpose(0, 2, 1, 3)
    costs = _costs(
        ("flash_attention", qt.shape, kt.shape, str(qt.dtype), causal,
         window, bq, bk, interpret),
        lambda: _fa.flash_attention.lower(qt, kt, vt, causal=causal,
                                          window=window, bq=bq, bk=bk,
                                          interpret=interpret),
        lambda: _fa.cost_estimate(qt.shape, kt.shape[1], qt.dtype.itemsize,
                                  causal=causal, window=window, bq=bq,
                                  bk=bk),
        interpret)
    with m.region("kernel:flash_attention", counters=costs):
        o = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                                bq=bq, bk=bk, interpret=interpret)
        o = jax.block_until_ready(o)
    return o.transpose(0, 2, 1, 3)


def fused_rmsnorm(x, scale, *, eps: float = 1e-5, interpret: bool = False):
    m = _markers
    if m is None or not _eager(x, scale):
        return _rms.rmsnorm(x, scale, eps=eps, interpret=interpret)
    costs = _costs(
        ("rmsnorm", x.shape, str(x.dtype), interpret),
        lambda: _rms.rmsnorm.lower(x, scale, eps=eps, interpret=interpret),
        lambda: _rms.cost_estimate(x.shape, x.dtype.itemsize),
        interpret)
    with m.region("kernel:rmsnorm", counters=costs):
        o = _rms.rmsnorm(x, scale, eps=eps, interpret=interpret)
        o = jax.block_until_ready(o)
    return o


def ssd_chunked_kernel(x, dt_log_decay, b_mat, c_mat, *, chunk: int = 128,
                       interpret: bool = False):
    """Kernel-backed drop-in for models.ssm.ssd_chunked (zero init state).

    x: (B, L, H, P); dt_log_decay: (B, L, H); b/c: (B, L, H, N).
    Returns y: (B, L, H, P) (no final state — training path).
    """
    xt = x.transpose(0, 2, 1, 3)
    at = dt_log_decay.transpose(0, 2, 1)
    bt = b_mat.transpose(0, 2, 1, 3)
    ct = c_mat.transpose(0, 2, 1, 3)
    m = _markers
    if m is None or not _eager(x, dt_log_decay, b_mat, c_mat):
        y = _ssd.ssd_scan(xt, at, bt, ct, chunk=chunk, interpret=interpret)
        return y.transpose(0, 2, 1, 3)
    costs = _costs(
        ("ssd_scan", xt.shape, bt.shape, str(xt.dtype), chunk, interpret),
        lambda: _ssd.ssd_scan.lower(xt, at, bt, ct, chunk=chunk,
                                    interpret=interpret),
        lambda: _ssd.cost_estimate(xt.shape, bt.shape[-1],
                                   xt.dtype.itemsize, chunk=chunk),
        interpret)
    with m.region("kernel:ssd_scan", counters=costs):
        y = _ssd.ssd_scan(xt, at, bt, ct, chunk=chunk, interpret=interpret)
        y = jax.block_until_ready(y)
    return y.transpose(0, 2, 1, 3)

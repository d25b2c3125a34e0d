"""Attention: GQA / MLA / sliding-window, train + prefill + decode paths.

Design notes
------------
* **train** (seq <= ~8k): on one TPU, the Pallas flash kernels (forward
  and backward; the S^2 scores stay in VMEM); elsewhere plain masked
  attention, whose S^2 logits are transient inside a rematted layer.
  ``select_attn_impl`` picks between them.
* **prefill** (32k): k-chunked online-softmax attention (flash-style in pure
  XLA) so the S^2 logits never materialize at once.  No bwd needed.
* **decode**: one query token against the KV cache, direct einsum; the cache
  sequence axis may be sharded (GSPMD inserts the partial-softmax
  collectives).
* **sliding window** uses a ring-buffer cache of ``window`` slots; absolute
  positions are reconstructed from ``pos`` so masking stays exact.
* **MLA** (DeepSeek-V2) caches the compressed latent ``c_kv`` + shared
  ``k_rope`` and uses the weight-absorption trick at decode time.

Shapes: x (B, S, d); q (B, S, H, D); k/v (B, S, KV, D); H = KV * G.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_norm, apply_rope
from repro.models.params import spec

NEG_INF = -2.0 ** 30   # large-but-finite; keeps softmax NaN-free on empty rows


# --------------------------------------------------------------------------
# Parameter specs
# --------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig, num_kv_heads: Optional[int] = None):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    kv = num_kv_heads or cfg.num_kv_heads
    return {
        "wq": spec((d, h, hd), ("embed", "heads", None)),
        "wk": spec((d, kv, hd), ("embed", "kv_heads", None)),
        "wv": spec((d, kv, hd), ("embed", "kv_heads", None)),
        "wo": spec((h, hd, d), ("heads", None, "embed")),
    }


def mla_specs(cfg: ModelConfig):
    a = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = a.qk_nope_head_dim + a.qk_rope_head_dim
    return {
        "wq_a": spec((d, a.q_lora_rank), ("embed", "q_lora")),
        "q_norm": spec((a.q_lora_rank,), ("q_lora",), init="ones"),
        "wq_b": spec((a.q_lora_rank, h, qk), ("q_lora", "heads", None)),
        "wkv_a": spec((d, a.kv_lora_rank + a.qk_rope_head_dim),
                      ("embed", "kv_lora")),
        "kv_norm": spec((a.kv_lora_rank,), ("kv_lora",), init="ones"),
        "wkv_b": spec((a.kv_lora_rank, h, a.qk_nope_head_dim + a.v_head_dim),
                      ("kv_lora", "heads", None)),
        "wo": spec((h, a.v_head_dim, d), ("heads", None, "embed")),
    }


# --------------------------------------------------------------------------
# Mask helpers
# --------------------------------------------------------------------------


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int,
               kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """Additive bias (0 / NEG_INF) of shape (..., Sq, Sk) from positions."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), dtype=bool)
    if causal:
        ok &= kp <= qp
    if window:
        ok &= kp > qp - window
    if kv_valid is not None:
        ok &= kp < kv_valid
    ok &= kp >= 0
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _softcap(scores, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


# --------------------------------------------------------------------------
# Core attention computations
# --------------------------------------------------------------------------


def _group(q, num_kv):
    """(B, Sq, H, D) -> (B, KV, G, Sq, D)."""
    b, s, h, dd = q.shape
    g = h // num_kv
    return q.reshape(b, s, num_kv, g, dd).transpose(0, 2, 3, 1, 4)


def _ungroup(o):
    """(B, KV, G, Sq, D) -> (B, Sq, H, D)."""
    b, kv, g, s, dd = o.shape
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s, kv * g, dd)


def full_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                   kv_valid=None, softcap=0.0, k_pos=None):
    """Plain masked attention; fp32 softmax. q_offset: absolute position of
    q[0] (decode: pos). kv_valid: number of valid cache slots (scalar)."""
    b, sq, h, dd = q.shape
    kvh = k.shape[2]
    qg = _group(q, kvh)                                  # (B,KV,G,Sq,D)
    kk = k.transpose(0, 2, 1, 3)                         # (B,KV,Sk,D)
    vv = v.transpose(0, 2, 1, 3)
    scores = jnp.einsum("bkgqd,bksd->bkgqs", qg, kk,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores * (1.0 / math.sqrt(dd)), softcap)
    q_pos = q_offset + jnp.arange(sq)
    if k_pos is None:
        k_pos = jnp.arange(k.shape[1])
    scores = scores + _mask_bias(q_pos, k_pos, causal=causal, window=window,
                                 kv_valid=kv_valid)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, vv)
    return _ungroup(out)


def chunked_attention(q, k, v, *, causal=True, window=0, chunk_k=1024,
                      softcap=0.0):
    """K-chunked online-softmax attention (prefill path, memory-bounded).

    Equivalent to full_attention; the (Sq, Sk) score matrix only ever exists
    one (Sq, chunk_k) slab at a time inside the scan.
    """
    b, sq, h, dd = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    if sk % chunk_k != 0:
        # fall back (shapes in this repo are powers of two; smoke sizes may not
        # divide the default chunk)
        chunk_k = math.gcd(sk, chunk_k) or sk
    nk = sk // chunk_k
    dv = v.shape[-1]
    qg = _group(q, kvh)                                   # (B,KV,G,Sq,D)
    kc = k.transpose(0, 2, 1, 3).reshape(b, kvh, nk, chunk_k, dd)
    vc = v.transpose(0, 2, 1, 3).reshape(b, kvh, nk, chunk_k, dv)
    kc = jnp.moveaxis(kc, 2, 0)                           # (nk,B,KV,ck,D)
    vc = jnp.moveaxis(vc, 2, 0)
    q_pos = jnp.arange(sq)
    scale = 1.0 / math.sqrt(dd)

    def body(carry, xs):
        m, l, acc = carry
        k_blk, v_blk, j = xs
        s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k_blk,
                       preferred_element_type=jnp.float32)
        s = _softcap(s * scale, softcap)
        k_pos = j * chunk_k + jnp.arange(chunk_k)
        s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p.astype(q.dtype), v_blk).astype(jnp.float32)
        return (m_new, l, acc), None

    g = h // kvh
    m0 = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, kvh, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _ungroup(out.astype(q.dtype))


def merge_partial(parts):
    """Merge (m, l, acc) partial-softmax triples (for the recursive causal
    decomposition used by the perf hillclimb)."""
    m = parts[0][0]
    for p in parts[1:]:
        m = jnp.maximum(m, p[0])
    l = sum(jnp.exp(pm - m) * pl for pm, pl, _ in parts)
    acc = sum(jnp.exp(pm - m)[..., None] * pa for pm, pl, pa in parts)
    return m, l, acc


def _partial_full(q, k, v, *, causal, q_offset, k_offset, softcap=0.0):
    """Un-normalized attention stats (m, l, acc) of q against k/v slice."""
    b, sq, h, dd = q.shape
    kvh = k.shape[2]
    qg = _group(q, kvh)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, kk,
                   preferred_element_type=jnp.float32)
    s = _softcap(s * (1.0 / math.sqrt(dd)), softcap)
    if causal:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = k_offset + jnp.arange(k.shape[1])
        s = s + _mask_bias(q_pos, k_pos, causal=True, window=0)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(q.dtype), vv
                     ).astype(jnp.float32)
    return m, l, acc


def recursive_causal_attention(q, k, v, *, levels=3, softcap=0.0,
                               q_offset=0, k_offset=0):
    """FLOP-exact causal attention via recursive block decomposition.

    causal(S) = causal(S/2 lower) + dense(q_hi x k_lo) + causal(S/2 upper);
    the dense block has no masked-out work, so wasted FLOPs drop from ~50%
    (full masked) to S^2/2^(levels+1).  This is the XLA-path analogue of a
    flash kernel's block skipping — used by the §Perf hillclimb.
    """
    def stats(q, k, v, level, q_off, k_off):
        sq = q.shape[1]
        if level == 0 or sq <= 128 or sq % 2:
            return _partial_full(q, k, v, causal=True, q_offset=q_off,
                                 k_offset=k_off, softcap=softcap)
        half = sq // 2
        q_lo, q_hi = q[:, :half], q[:, half:]
        k_lo, k_hi = k[:, :half], k[:, half:]
        v_lo, v_hi = v[:, :half], v[:, half:]
        m1, l1, a1 = stats(q_lo, k_lo, v_lo, level - 1, q_off, k_off)
        # strictly-lower dense rectangle: q_hi attends all of k_lo, unmasked
        m2, l2, a2 = _partial_full(q_hi, k_lo, v_lo, causal=False,
                                   q_offset=0, k_offset=0, softcap=softcap)
        m3, l3, a3 = stats(q_hi, k_hi, v_hi, level - 1, q_off + half,
                           k_off + half)
        m_hi, l_hi, a_hi = merge_partial([(m2, l2, a2), (m3, l3, a3)])
        m = jnp.concatenate([m1, m_hi], axis=-1)
        l = jnp.concatenate([l1, l_hi], axis=-1)
        a = jnp.concatenate([a1, a_hi], axis=-2)
        return m, l, a

    m, l, acc = stats(q, k, v, levels, q_offset, k_offset)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return _ungroup(out.astype(q.dtype))


# --------------------------------------------------------------------------
# GQA block (projections + rope + cache + attention)
# --------------------------------------------------------------------------


def select_attn_impl(attn_impl: str, cfg: ModelConfig, seq_len: int, *,
                     mesh_devices: int, backend: str) -> str:
    """The train path's attention implementation.  ``"auto"`` takes the
    Pallas flash kernels where they serve: a TPU backend, attention inputs
    on one device (a ``pallas_call`` is not partitioned by GSPMD), GQA
    without a logit softcap, and a sequence and head dim the kernel's
    blocks tile.  Everything else, the CPU backend included, runs
    ``full_attention``.  Any other value is taken as given."""
    if attn_impl != "auto":
        return attn_impl
    from repro.kernels.flash_attention import fits
    if (backend == "tpu" and mesh_devices <= 1
            and cfg.attention_type == "gqa" and not cfg.attn_logit_softcap
            and fits(seq_len, cfg.head_dim)):
        return "flash"
    return "masked"


def _ring_slots(pos, window):
    """Absolute positions stored in each ring-buffer slot given next-token
    index ``pos`` (scalar): slot s holds position p = largest value < pos with
    p ≡ s (mod window); negative -> never written."""
    s = jnp.arange(window)
    p = pos - 1 - jnp.mod(pos - 1 - s, window)
    return p                                             # (window,), may be <0


def onehot_update(cache, new, slot):
    """Write ``new`` (B, 1, ...) into ``cache`` (B, S, ...) at dynamic ``slot``.

    Fully elementwise along the sequence axis — unlike dynamic_update_slice
    this stays collective-free under GSPMD when the cache's sequence dim is
    sharded (the decode path for GQA models whose kv_heads < TP axis)."""
    s = cache.shape[1]
    oh = (jnp.arange(s) == slot)
    oh = oh.reshape((1, s) + (1,) * (cache.ndim - 2))
    return jnp.where(oh, new.astype(cache.dtype), cache)


def _cache_write(cache_arr, new, slot, cache_update: str):
    """Decode cache write: in-place DUS when the sequence axis is unsharded
    (cheapest — aliases the buffer), one-hot select when it is sharded
    (collective-free under GSPMD)."""
    if cache_update == "dus":
        return jax.lax.dynamic_update_slice_in_dim(
            cache_arr, new.astype(cache_arr.dtype), slot, axis=1)
    return onehot_update(cache_arr, new, slot)


@jax.named_scope("attention")
def gqa_attention(p, x, cfg: ModelConfig, *, rope=None, mode="train",
                  cache=None, pos=None, attn_impl="masked",
                  kv_out_constraint=None, bidirectional=False,
                  cache_update="onehot"):
    """Full GQA attention block.

    mode: "train" | "prefill" | "decode".
    rope: (cos, sin) tables matching x's sequence positions, or None.
    cache (prefill out / decode in-out): {"k","v"} ring- or full-buffer.
    pos: scalar int32 — number of tokens already in the cache (decode).
    Returns (out, new_cache).
    """
    dt = x.dtype
    b, s, d = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if rope is not None:
        cos, sin = rope
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    window = cfg.sliding_window
    causal = not bidirectional
    new_cache = None

    if mode == "train":
        if attn_impl == "flash" and not cfg.attn_logit_softcap:
            # Pallas blocked online-softmax kernel (TPU Mosaic; interpret
            # mode on the CPU backend only).  S^2 scores never leave VMEM —
            # see kernels/flash_attention.py.
            from repro.kernels.ops import flash_attention_bshd
            interpret = jax.default_backend() == "cpu"
            out = flash_attention_bshd(q, k, v, causal=causal,
                                       window=window, interpret=interpret)
        elif attn_impl == "recursive" and causal and s >= 512:
            out = recursive_causal_attention(q, k, v,
                                             softcap=cfg.attn_logit_softcap)
            if window:
                # recursive path does not support SWA; fall back
                out = full_attention(q, k, v, causal=causal, window=window,
                                     softcap=cfg.attn_logit_softcap)
        else:
            out = full_attention(q, k, v, causal=causal, window=window,
                                 softcap=cfg.attn_logit_softcap)
    elif mode == "prefill":
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                softcap=cfg.attn_logit_softcap)
        if cache is not None:
            if window and window < s:
                slots = jnp.mod(jnp.arange(s - window, s), window)
                ck = cache["k"].at[:, slots].set(k[:, -window:].astype(cache["k"].dtype))
                cv = cache["v"].at[:, slots].set(v[:, -window:].astype(cache["v"].dtype))
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
            if kv_out_constraint is not None:
                ck, cv = kv_out_constraint(ck), kv_out_constraint(cv)
            new_cache = {"k": ck, "v": cv}
    elif mode == "decode":
        assert cache is not None and pos is not None
        cache_len = cache["k"].shape[1]
        if window and cache_len == window:
            slot = jnp.mod(pos, window)
            ck = _cache_write(cache["k"], k, slot, cache_update)
            cv = _cache_write(cache["v"], v, slot, cache_update)
            # ring slots hold absolute positions <= pos; causal+window+
            # kp>=0 masking reconstructs exact SWA semantics
            out = full_attention(q, ck.astype(dt), cv.astype(dt),
                                 causal=True, window=window, q_offset=pos,
                                 softcap=cfg.attn_logit_softcap,
                                 k_pos=_ring_slots(pos + 1, window))
        else:
            ck = _cache_write(cache["k"], k, pos, cache_update)
            cv = _cache_write(cache["v"], v, pos, cache_update)
            out = full_attention(q, ck.astype(dt), cv.astype(dt),
                                 causal=False, window=window,
                                 kv_valid=pos + 1, q_offset=pos,
                                 softcap=cfg.attn_logit_softcap)
        new_cache = {"k": ck, "v": cv}
    else:
        raise ValueError(mode)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return y, new_cache


@jax.named_scope("attention")
def cross_attention(p, x, kv_cache, cfg: ModelConfig):
    """Decoder cross-attention against precomputed encoder K/V."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    out = full_attention(q, kv_cache["k"].astype(dt), kv_cache["v"].astype(dt),
                         causal=False, window=0)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def cross_kv(p, enc_out, cfg: ModelConfig):
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    return {"k": k, "v": v}


# --------------------------------------------------------------------------
# MLA block (DeepSeek-V2)
# --------------------------------------------------------------------------


@jax.named_scope("attention")
def mla_attention(p, x, cfg: ModelConfig, *, rope, mode="train", cache=None,
                  pos=None, attn_impl="masked", cache_update="onehot"):
    """Multi-head Latent Attention.

    train/prefill: decompress latent to per-head K/V (compute-optimal).
    decode: weight absorption — attention runs in the kv_lora space, so the
    cache is (B, S, kv_lora + rope_dim) regardless of head count.
    """
    a = cfg.mla
    dt = x.dtype
    b, s, d = x.shape
    h = cfg.num_heads
    cos, sin = rope

    q_lat = apply_norm({"scale": p["q_norm"]}, x @ p["wq_a"].astype(dt),
                       cfg, eps=1e-6)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"].astype(dt))
    q_nope, q_rope = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, cos, sin)

    kv_a = x @ p["wkv_a"].astype(dt)                       # (B,S,lora+rope)
    c_kv = apply_norm({"scale": p["kv_norm"]}, kv_a[..., :a.kv_lora_rank],
                      cfg, eps=1e-6)
    k_rope = kv_a[..., None, a.kv_lora_rank:]              # (B,S,1,rope)
    k_rope = apply_rope(k_rope, cos, sin)[..., 0, :]       # shared across heads

    wkv_b = p["wkv_b"].astype(dt)                          # (lora,H,nope+v)
    w_k = wkv_b[..., :a.qk_nope_head_dim]                  # (lora,H,nope)
    w_v = wkv_b[..., a.qk_nope_head_dim:]                  # (lora,H,v)

    if mode in ("train", "prefill"):
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, w_k)
        v = jnp.einsum("bsr,rhk->bshk", c_kv, w_v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (b, s, h, a.qk_rope_head_dim))], axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        if mode == "train":
            if attn_impl == "recursive" and s >= 512:
                out = recursive_causal_attention(qq, k, v)
            else:
                out = full_attention(qq, k, v, causal=True)
        else:
            out = chunked_attention(qq, k, v, causal=True)
        new_cache = None
        if mode == "prefill" and cache is not None:
            ckv = jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], c_kv.astype(cache["ckv"].dtype), 0, axis=1)
            krope = jax.lax.dynamic_update_slice_in_dim(
                cache["krope"], k_rope.astype(cache["krope"].dtype), 0, axis=1)
            new_cache = {"ckv": ckv, "krope": krope}
    else:  # decode, absorbed
        assert cache is not None and pos is not None
        ckv = _cache_write(cache["ckv"], c_kv, pos, cache_update)
        krope = _cache_write(cache["krope"], k_rope, pos, cache_update)
        new_cache = {"ckv": ckv, "krope": krope}
        # absorb: q_eff[h] = q_nope[h] @ w_k[:, h, :]^T  -> lora space
        q_eff = jnp.einsum("bshk,rhk->bshr", q_nope, w_k)
        s_lat = jnp.einsum("bshr,btr->bhst", q_eff, ckv.astype(dt),
                           preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bshk,btk->bhst", q_rope, krope.astype(dt),
                            preferred_element_type=jnp.float32)
        scores = (s_lat + s_rope) / math.sqrt(a.qk_nope_head_dim
                                              + a.qk_rope_head_dim)
        k_pos = jnp.arange(ckv.shape[1])
        scores = scores + _mask_bias(pos + jnp.arange(s), k_pos, causal=False,
                                     window=0, kv_valid=pos + 1)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, ckv.astype(dt))
        out = jnp.einsum("bshr,rhk->bshk", o_lat, w_v)

    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return y, new_cache

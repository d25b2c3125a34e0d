"""Shared neural-net layers: norms, RoPE (incl. M-RoPE), MLPs, embeddings.

All functions are pure; parameters are plain dict pytrees built from
:mod:`repro.models.params` specs.  Compute dtype is configurable (bf16 on
TPU); parameters stay in ``param_dtype`` (fp32) and are cast at use sites.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import spec

# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def norm_specs(cfg: ModelConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": spec((d,), ("norm",), init="ones"),
                "bias": spec((d,), ("norm",), init="zeros")}
    return {"scale": spec((d,), ("norm",), init="ones")}


@jax.named_scope("norm")
def apply_norm(p, x, cfg: ModelConfig, eps: Optional[float] = None):
    eps = eps or cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


@jax.named_scope("norm")
def rmsnorm_gated(scale, x, gate, eps: float = 1e-5):
    """Mamba2-style gated RMSNorm: norm(x * silu(gate)) * scale."""
    x = x * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE (standard + Qwen2-VL M-RoPE)
# --------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_table(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for integer positions.

    positions: (..., S) int32 -> cos, sin: (..., S, head_dim // 2) fp32
    """
    freqs = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def mrope_table(positions: jax.Array, head_dim: int, theta: float,
                sections) -> tuple:
    """Qwen2-VL multimodal RoPE: positions (..., S, 3) for (t, h, w).

    The head_dim/2 frequency bands are split into ``sections`` (t/h/w);
    each band takes its angle from the corresponding position component.
    Returns cos, sin of shape (..., S, head_dim // 2).
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(head_dim, theta)                       # (half,)
    # component index per frequency band
    comp = jnp.concatenate([
        jnp.full((s,), i, dtype=jnp.int32) for i, s in enumerate(sections)])
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),
        jnp.broadcast_to(comp, positions.shape[:-1] + (half,)).astype(jnp.int32),
        axis=-1)                                              # (..., S, half)
    ang = pos * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos).

    x: (..., S, H, D); cos/sin: (..., S, half) broadcast over heads.
    Uses the "split halves" convention (llama): x1 = x[..., :D/2].
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(x.dtype)   # (..., S, 1, half)
    s = sin[..., None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_type == "swiglu":
        return {
            "w_gate": spec((d, ff), ("embed", "mlp")),
            "w_up": spec((d, ff), ("embed", "mlp")),
            "w_down": spec((ff, d), ("mlp", "embed")),
        }
    # gelu / relu2: two matrices
    return {
        "w_up": spec((d, ff), ("embed", "mlp")),
        "w_down": spec((ff, d), ("mlp", "embed")),
    }


@jax.named_scope("mlp")
def apply_mlp(p, x, cfg: ModelConfig):
    dt = x.dtype
    if cfg.mlp_type == "swiglu":
        g = x @ p["w_gate"].astype(dt)
        u = x @ p["w_up"].astype(dt)
        h = jax.nn.silu(g) * u
    elif cfg.mlp_type == "gelu":
        h = jax.nn.gelu(x @ p["w_up"].astype(dt))
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"].astype(dt)))
    else:
        raise ValueError(cfg.mlp_type)
    return h @ p["w_down"].astype(dt)


# --------------------------------------------------------------------------
# Embedding / LM head / loss
# --------------------------------------------------------------------------


def embedding_specs(cfg: ModelConfig):
    v, d = cfg.vocab_padded, cfg.d_model
    out = {"embedding": spec((v, d), ("vocab", "embed"), scale=0.02)}
    if not cfg.tie_embeddings:
        out["lm_head"] = spec((d, v), ("embed", "vocab"))
    return out


@jax.named_scope("embed")
def embed_tokens(p, tokens, cfg: ModelConfig):
    return p["embedding"].astype(jnp.dtype(cfg.dtype))[tokens]


@jax.named_scope("logits_loss")
def lm_logits(p, h, cfg: ModelConfig):
    if cfg.tie_embeddings:
        w = p["embedding"].astype(h.dtype).T
    else:
        w = p["lm_head"].astype(h.dtype)
    return h @ w


def cross_entropy(logits, targets, cfg: ModelConfig, mask=None):
    """Mean CE over valid targets; padded vocab entries are masked out.

    logits: (B, S, vocab_padded); targets: (B, S) int32.
    """
    lf = logits.astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
        lf = jnp.where(pad, -1e9, lf)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

"""Plain reference of the sparse-MoE decoder's forward pass.

Written from the configuration alone and imports nothing of the program:
RMSNorm, rotary GQA attention over the whole sequence (causal, one
sequence at a time), top-k routing (softmax over the experts, the k
largest renormalised) with every routed token computed by its experts and
none dropped, the untied LM head over the real vocabulary.  Float32 at
``highest`` matmul precision; the bfloat16 weights are widened one layer
and one expert at a time, so the pass fits beside them on one chip.  An
expert is computed on every token and its output weighted by the token's
gate (zero where the token is not routed to it), which is the same sum.

``mm_dtype`` gives the control: every matmul operand per-tensor scaled
through that narrow float type.

Beside the logits, the pass gives each position's routing margin: the
least, over the layers, of the router's second-largest probability less
its third, i.e. how close the position's choice of experts came to a tie.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9


def _q(x, dtype):
    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _mm(eq, a, b, mm_dtype):
    return jnp.einsum(eq, _q(a.astype(jnp.float32), mm_dtype),
                      _q(b.astype(jnp.float32), mm_dtype),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x1 * sn + x2 * c], axis=-1)


def _attention(h, a, cfg, mm_dtype):
    s = h.shape[0]
    kvh, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    g = cfg["num_attention_heads"] // kvh
    q = _rope(_mm("sd,dhk->shk", h, a["wq"], mm_dtype), cfg["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", h, a["wk"], mm_dtype), cfg["rope_theta"])
    v = _mm("sd,dhk->shk", h, a["wv"], mm_dtype)
    q = q.reshape(s, kvh, g, hd)
    sc = _mm("qkgd,skd->kgqs", q, k, mm_dtype) / math.sqrt(hd)
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if cfg.get("sliding_window"):
        ok &= pos[None, :] > pos[:, None] - cfg["sliding_window"]
    sc = jnp.where(ok, sc, NEG)
    o = _mm("kgqs,skd->qkgd", jax.nn.softmax(sc, axis=-1), v, mm_dtype)
    return _mm("shk,hkd->sd", o.reshape(s, -1, hd), a["wo"], mm_dtype)


def _moe(h, m, cfg, mm_dtype):
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("sd,de->se", h, m["router"], mm_dtype),
                           axis=-1)
    ranked = jax.lax.top_k(probs, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, e) * top[..., None], axis=1)  # (S,E)

    def expert(acc, i):
        u = jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"][i], mm_dtype)) \
            * _mm("sd,df->sf", h, m["w_up"][i], mm_dtype)
        y = _mm("sf,fd->sd", u, m["w_down"][i], mm_dtype)
        return acc + gate[:, i, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(e))
    return out, margin


@partial(jax.jit, static_argnums=(2, 3))
def logits(params, tokens, cfg_items: tuple, mm_dtype=None):
    """(S,) token ids -> ((S, vocab_size) float32 logits, (S,) routing
    margins)."""
    cfg = dict(cfg_items)
    eps = cfg["rms_norm_eps"]
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    layers = params["moe_layers"]
    margin = jnp.full(tokens.shape, jnp.inf)
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda w: w[i], layers)
        x = x + _attention(_rms(x, lp["ln1"]["scale"], eps), lp["attn"], cfg,
                           mm_dtype)
        y, m = _moe(_rms(x, lp["ln2"]["scale"], eps), lp["moe"], cfg,
                    mm_dtype)
        x, margin = x + y, jnp.minimum(margin, m)
    x = _rms(x, params["final_norm"]["scale"], eps)
    out = _mm("sd,dv->sv", x, params["embed"]["lm_head"], mm_dtype)
    return out[:, :cfg["vocab_size"]], margin


def cfg_key(conf: dict) -> tuple:
    """The hashable part of a configuration the forward pass reads."""
    keys = ("hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
            "rms_norm_eps", "num_local_experts", "num_experts_per_tok",
            "sliding_window")
    return tuple((k, conf[k]) for k in keys)


def token_gaps(ref_logits, positions, tokens):
    """For each token, the gap by which its reference logit lies below the
    reference's best at its position."""
    rows = ref_logits[jnp.asarray(positions)]
    best = jnp.max(rows, axis=-1)
    got = jnp.take_along_axis(rows, jnp.asarray(tokens)[:, None], axis=-1)
    return jax.device_get(best - got[:, 0])


def rel_errors(logits, ref_rows):
    """For each position (row), ``|logits - ref| / |ref|`` in L2 norms."""
    a = np.asarray(logits, np.float64)
    b = np.asarray(ref_rows, np.float64)
    return np.linalg.norm(a - b, axis=-1) / np.linalg.norm(b, axis=-1)

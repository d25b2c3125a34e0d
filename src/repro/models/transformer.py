"""Model assembly: decoder-only / enc-dec / SSM / hybrid LMs.

One entry point, :func:`forward`, serves all 10 assigned architectures in all
three execution modes (train / prefill / decode).  Layers are *scanned* with
stacked parameters — essential to keep HLO size and compile time flat across
60–96-layer configs.

Caches are pytrees stacked over the layer axis, so the same scan carries
them; decode-time cache writes are one-hot selects (GSPMD-safe when the cache
sequence axis is sharded, see ``attention.onehot_update``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (
    attn_specs, cross_attention, cross_kv, gqa_attention, mla_attention,
    mla_specs, select_attn_impl)
from repro.models.layers import (
    apply_mlp, apply_norm, cross_entropy, embed_tokens, embedding_specs,
    lm_logits, mlp_specs, mrope_table, norm_specs, rope_table)
from repro.models.moe import apply_moe, moe_specs
from repro.models.params import abstract_params, init_params, spec, stack_specs
from repro.parallel.sharding import NullConstraints


# ==========================================================================
# Per-layer specs
# ==========================================================================


def _attn_block_specs(cfg: ModelConfig, mlp_override: Optional[int] = None,
                      moe_layer: bool = False, cross: bool = False):
    out = {"ln1": norm_specs(cfg)}
    if cfg.attention_type == "mla":
        out["attn"] = mla_specs(cfg)
    else:
        out["attn"] = attn_specs(cfg)
    if cross:
        out["ln_cross"] = norm_specs(cfg)
        out["cross"] = attn_specs(cfg)
    out["ln2"] = norm_specs(cfg)
    if moe_layer:
        out["moe"] = moe_specs(cfg)
    else:
        out["mlp"] = mlp_specs(cfg, d_ff=mlp_override)
    return out


def _layer_plan(cfg: ModelConfig) -> dict:
    """How many layers of each kind, as stacked groups."""
    if cfg.family == "ssm":                               # rwkv6
        return {"rwkv": cfg.num_layers}
    if cfg.family == "hybrid":
        n_groups = cfg.num_layers // cfg.hybrid.attn_every
        rem = cfg.num_layers - n_groups * cfg.hybrid.attn_every
        return {"hybrid_groups": n_groups, "hybrid_rem": rem}
    if cfg.moe is not None:
        return {"dense": cfg.moe.num_dense_layers,
                "moe": cfg.num_layers - cfg.moe.num_dense_layers}
    return {"dense": cfg.num_layers}


def model_specs(cfg: ModelConfig):
    """Full parameter-spec tree (stacked layers)."""
    plan = _layer_plan(cfg)
    out: dict = {"embed": embedding_specs(cfg),
                 "final_norm": norm_specs(cfg)}

    if cfg.family == "ssm":
        blk = ssm_mod.rwkv6_specs(cfg)
        out["layers"] = stack_specs(blk, plan["rwkv"])
    elif cfg.family == "hybrid":
        mamba = ssm_mod.mamba2_specs(cfg)
        mamba = {"ln": norm_specs(cfg), **mamba}
        ae = cfg.hybrid.attn_every
        if plan["hybrid_groups"]:
            out["groups"] = stack_specs(
                stack_specs(mamba, ae, "inner_layers"),
                plan["hybrid_groups"])
        if plan["hybrid_rem"]:
            out["rem"] = stack_specs(mamba, plan["hybrid_rem"])
        out["shared"] = stack_specs(_attn_block_specs(cfg),
                                    cfg.hybrid.num_shared_blocks)
    else:
        if plan.get("dense"):
            dff = cfg.moe.d_ff_dense if (cfg.moe is not None
                                         and cfg.moe.d_ff_dense) else None
            out["dense_layers"] = stack_specs(
                _attn_block_specs(cfg, mlp_override=dff), plan["dense"])
        if plan.get("moe"):
            out["moe_layers"] = stack_specs(
                _attn_block_specs(cfg, moe_layer=True), plan["moe"])

    if cfg.family == "encdec":
        enc_cfg = dataclasses.replace(cfg)
        out["encoder"] = {
            "layers": stack_specs(_attn_block_specs(enc_cfg),
                                  cfg.num_encoder_layers),
            "final_norm": norm_specs(cfg),
        }
        # decoder self-attn blocks get a cross-attention sublayer
        dff = None
        out.pop("dense_layers", None)
        out["dec_layers"] = stack_specs(
            _attn_block_specs(cfg, mlp_override=dff, cross=True),
            cfg.num_layers)
    return out


# ==========================================================================
# Caches — spec'd with logical axes (single source of truth for shapes,
# shardings and zero-init; mirrors the params system)
# ==========================================================================


def cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16):
    """ParamSpec tree for the decode caches (all zero-init).

    Logical axes drive the cache shardings: KV caches shard batch over DP
    and kv_heads over TP, falling back to the cache sequence dim when
    kv_heads does not divide (see ``_AXIS_PRIORITY`` in parallel.sharding).
    """
    plan = _layer_plan(cfg)
    kv_len = min(max_len, cfg.sliding_window) if cfg.sliding_window \
        else max_len

    def attn_cache():
        if cfg.attention_type == "mla":
            a = cfg.mla
            return {"ckv": spec((batch, max_len, a.kv_lora_rank),
                                ("batch", "cache_seq", None), dtype,
                                init="zeros"),
                    "krope": spec((batch, max_len, a.qk_rope_head_dim),
                                  ("batch", "cache_seq", None), dtype,
                                  init="zeros")}
        kv = spec((batch, kv_len, cfg.num_kv_heads, cfg.head_dim),
                  ("batch", "cache_seq", "kv_heads", None), dtype,
                  init="zeros")
        return {"k": kv, "v": kv}

    def mamba_cache():
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.num_heads(cfg.d_model)
        conv_dim = di + 2 * s.n_groups * s.state_dim
        return {"conv": spec((batch, s.conv_width - 1, conv_dim),
                             ("batch", None, "inner"), dtype, init="zeros"),
                "ssm": spec((batch, nh, s.head_dim, s.state_dim),
                            ("batch", "ssm_heads", None, None), jnp.float32,
                            init="zeros")}

    def rwkv_cache():
        d = cfg.d_model
        nh = d // cfg.rwkv.head_dim
        return {"shift_tm": spec((batch, d), ("batch", "embed"), dtype,
                                 init="zeros"),
                "shift_cm": spec((batch, d), ("batch", "embed"), dtype,
                                 init="zeros"),
                "wkv": spec((batch, nh, cfg.rwkv.head_dim,
                             cfg.rwkv.head_dim),
                            ("batch", "ssm_heads", None, None), jnp.float32,
                            init="zeros")}

    if cfg.family == "ssm":
        return stack_specs(rwkv_cache(), plan["rwkv"])
    if cfg.family == "hybrid":
        out = {}
        ae = cfg.hybrid.attn_every
        if plan["hybrid_groups"]:
            out["groups"] = stack_specs(
                stack_specs(mamba_cache(), ae, "inner_layers"),
                plan["hybrid_groups"])
            out["shared_attn"] = stack_specs(attn_cache(),
                                             plan["hybrid_groups"])
        if plan["hybrid_rem"]:
            out["rem"] = stack_specs(mamba_cache(), plan["hybrid_rem"])
        return out
    if cfg.family == "encdec":
        cross = spec((batch, cfg.encdec_source_len, cfg.num_kv_heads,
                      cfg.head_dim),
                     ("batch", "cache_seq", "kv_heads", None), dtype,
                     init="zeros")
        return {"self": stack_specs(attn_cache(), cfg.num_layers),
                "cross": stack_specs({"k": cross, "v": cross},
                                     cfg.num_layers)}
    out = {}
    if plan.get("dense"):
        out["dense"] = stack_specs(attn_cache(), plan["dense"])
    if plan.get("moe"):
        out["moe"] = stack_specs(attn_cache(), plan["moe"])
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Concrete zero caches matching forward()'s scan layout."""
    return init_params(cache_specs(cfg, batch, max_len, dtype))


# ==========================================================================
# Blocks
# ==========================================================================


ZERO_AUX = {"moe_aux_loss": 0.0, "moe_dropped_frac": 0.0, "moe_max_load": 0.0}


def _zero_aux():
    return {k: jnp.float32(v) for k, v in ZERO_AUX.items()}


def _attn_block(p, x, cfg, *, rope, mode, cache, pos, pc, attn_impl,
                moe_layer=False, cross_kv_cache=None, bidirectional=False,
                cache_update="onehot"):
    """Pre-norm transformer block; returns (x, new_cache, aux)."""
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.attention_type == "mla":
        y, new_cache = mla_attention(p["attn"], h, cfg, rope=rope, mode=mode,
                                     cache=cache, pos=pos,
                                     attn_impl=attn_impl,
                                     cache_update=cache_update)
    else:
        y, new_cache = gqa_attention(
            p["attn"], h, cfg, rope=rope, mode=mode, cache=cache, pos=pos,
            attn_impl=attn_impl, bidirectional=bidirectional,
            cache_update=cache_update,
            kv_out_constraint=(pc.kv_cache if pc is not None else None))
    x = x + y
    if cross_kv_cache is not None:
        h = apply_norm(p["ln_cross"], x, cfg)
        x = x + cross_attention(p["cross"], h, cross_kv_cache, cfg)
    h = apply_norm(p["ln2"], x, cfg)
    aux = _zero_aux()
    if moe_layer:
        y, moe_aux = apply_moe(p["moe"], h, cfg, pc=pc)
        aux.update({k: jnp.asarray(v, jnp.float32)
                    for k, v in moe_aux.items()})
    else:
        y = apply_mlp(p["mlp"], h, cfg)
    x = x + y
    if pc is not None:
        x = pc.tokens(x)
    return x, new_cache, aux


def _rwkv_block(p, x, cfg, *, mode, cache):
    ln_tm = {"scale": p["ln_tm_scale"], "bias": p["ln_tm_bias"]}
    ln_cm = {"scale": p["ln_cm_scale"], "bias": p["ln_cm_bias"]}
    lcfg = dataclasses.replace(cfg, norm_type="layernorm")
    y, c_tm = ssm_mod.rwkv6_time_mix(p, apply_norm(ln_tm, x, lcfg), cfg,
                                     mode=mode, cache=cache)
    x = x + y
    y, c_cm = ssm_mod.rwkv6_channel_mix(p, apply_norm(ln_cm, x, lcfg), cfg,
                                        mode=mode, cache=cache)
    x = x + y
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {**cache, **(c_tm or {}), **(c_cm or {})}
    return x, new_cache


def _mamba_block(p, x, cfg, *, mode, cache, pc):
    h = apply_norm(p["ln"], x, cfg)
    y, new_cache = ssm_mod.mamba2_block(
        {k: v for k, v in p.items() if k != "ln"}, h, cfg,
        mode=mode, cache=cache)
    x = x + y
    if pc is not None:
        x = pc.tokens(x)
    return x, new_cache


# ==========================================================================
# Forward
# ==========================================================================


def _combine_aux(acc, aux):
    return {
        "moe_aux_loss": acc["moe_aux_loss"] + aux["moe_aux_loss"],
        "moe_dropped_frac": acc["moe_dropped_frac"] + aux["moe_dropped_frac"],
        "moe_max_load": jnp.maximum(acc["moe_max_load"], aux["moe_max_load"]),
    }


@jax.named_scope("attention")
def _rope_for(cfg: ModelConfig, positions, extras):
    if cfg.rope_type == "none":
        return None
    hd = cfg.mla.qk_rope_head_dim if cfg.attention_type == "mla" \
        else cfg.head_dim
    if cfg.rope_type == "mrope":
        mpos = extras["mrope_pos"]                        # (B, S, 3)
        return mrope_table(mpos, hd, cfg.rope_theta, cfg.mrope_sections)
    return rope_table(positions, hd, cfg.rope_theta)


def _sinusoidal(positions, d):
    """Absolute sinusoidal position encoding (enc-dec family)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _scan_layers(body, x, stacked_params, stacked_cache, *, remat="none"):
    """Scan ``body(x, layer_params, layer_cache) -> (x, new_cache, aux)``."""
    if remat != "none":
        policy = {"minimal": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                  "full": jax.checkpoint_policies.nothing_saveable}[remat]
        body = jax.checkpoint(body, policy=policy)

    def step(carry, xs):
        x, aux_acc = carry
        lp, lc = xs
        x, new_cache, aux = body(x, lp, lc)
        return (x, _combine_aux(aux_acc, aux)), new_cache

    (x, aux), new_caches = jax.lax.scan(
        step, (x, _zero_aux()), (stacked_params, stacked_cache))
    return x, new_caches, aux


def forward(params, cfg: ModelConfig, *, tokens, mode="train", cache=None,
            pos=None, pc=None, extras=None, attn_impl="auto",
            remat="none", cache_update="onehot"):
    """Run the model.

    tokens: (B, S) int32.  decode: S is the number of new tokens (1).
    cache: stacked cache pytree (prefill out / decode in-out).
    pos: scalar int32 — tokens already in the cache (decode only).
    extras: modality inputs — {"src_frames", "patches", "mrope_pos"}.
    Returns (logits, new_cache, aux).
    """
    pc = pc or NullConstraints()
    extras = extras or {}
    b, s = tokens.shape
    if mode == "train":
        attn_impl = select_attn_impl(
            attn_impl, cfg, s, backend=jax.default_backend(),
            mesh_devices=pc.mesh.devices.size if pc.mesh is not None else 1)
    if pos is None:
        positions = jnp.arange(s)[None, :]
    else:
        positions = pos + jnp.arange(s)[None, :]

    x = embed_tokens(params["embed"], tokens, cfg)
    if cfg.family == "vlm" and "patches" in extras:
        patches = extras["patches"].astype(x.dtype)       # (B, P, d)
        p_len = patches.shape[1]
        x = jnp.concatenate([x[:, :1], patches, x[:, 1 + p_len:]], axis=1)
    if cfg.family == "encdec":
        x = x + _sinusoidal(positions, cfg.d_model).astype(x.dtype)
    x = pc.tokens(x)

    rope = _rope_for(cfg, positions, extras)
    aux = _zero_aux()
    new_cache: Any = None

    # ---------------- family dispatch -------------------------------------
    if cfg.family == "ssm":
        def body(x, lp, lc):
            x, nc = _rwkv_block(lp, x, cfg, mode=mode,
                                cache=(None if mode == "train" else lc))
            return x, nc, _zero_aux()
        lc = cache if cache is not None else _dummy_cache(cfg, b, mode)
        x, new_cache, aux = _scan_layers(body, x, params["layers"], lc,
                                         remat=remat if mode == "train"
                                         else "none")

    elif cfg.family == "hybrid":
        x, new_cache, aux = _hybrid_forward(
            params, x, cfg, mode=mode, cache=cache, pos=pos, rope=rope,
            pc=pc, attn_impl=attn_impl, remat=remat,
            cache_update=cache_update)

    elif cfg.family == "encdec":
        x, new_cache, aux = _encdec_forward(
            params, x, cfg, mode=mode, cache=cache, pos=pos, pc=pc,
            extras=extras, attn_impl=attn_impl, remat=remat,
            cache_update=cache_update)

    else:
        new_cache = {}
        trem = remat if mode == "train" else "none"
        for group, key in (("dense_layers", "dense"), ("moe_layers", "moe")):
            if group not in params:
                continue
            moe_layer = key == "moe"

            def body(x, lp, lc, moe_layer=moe_layer):
                return _attn_block(lp, x, cfg, rope=rope, mode=mode,
                                   cache=(None if mode == "train" else lc),
                                   pos=pos, pc=pc, attn_impl=attn_impl,
                                   moe_layer=moe_layer,
                                   cache_update=cache_update)
            lc = cache[key] if cache is not None \
                else _dummy_cache(cfg, b, mode,
                                  n=jax.tree.leaves(params[group])[0].shape[0])
            x, nc, a = _scan_layers(body, x, params[group], lc, remat=trem)
            new_cache[key] = nc
            aux = _combine_aux(aux, a)
        if not any(k in params for k in ("dense_layers", "moe_layers")):
            raise ValueError("no layer groups in params")

    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["embed"], x, cfg)
    logits = pc.logits(logits)
    return logits, new_cache, aux


def _dummy_cache(cfg, batch, mode, n=None):
    """Scan requires an xs tree even when no cache flows (train mode)."""
    n = n if n is not None else cfg.num_layers
    return jnp.zeros((n, 0), jnp.float32)


# -- hybrid (zamba2) --------------------------------------------------------


def _hybrid_forward(params, x, cfg, *, mode, cache, pos, rope, pc, attn_impl,
                    remat, cache_update="onehot"):
    ae = cfg.hybrid.attn_every
    nsb = cfg.hybrid.num_shared_blocks
    aux = _zero_aux()
    new_cache = {}
    trem = remat if mode == "train" else "none"
    b = x.shape[0]

    if "groups" in params:
        n_groups = jax.tree.leaves(params["groups"])[0].shape[0]

        # The shared-attention caches are the dominant decode state (13 x
        # 500k KV at long context); they ride the scan CARRY with per-group
        # dynamic slice/update so XLA keeps one aliased buffer — as scan
        # xs/ys they would be double-buffered and re-stacked every step
        # (zamba2 long_500k memory term -~2x).
        def group_body(carry, xs):
            x, aux_acc, ac_all = carry
            gp, gc, gi = xs
            ac = None
            if ac_all is not None:
                ac = jax.tree.map(
                    lambda c: jax.lax.dynamic_index_in_dim(
                        c, gi, axis=0, keepdims=False), ac_all)

            def inner(x, lp, lc):
                x, nc = _mamba_block(lp, x, cfg, mode=mode,
                                     cache=(None if mode == "train" else lc),
                                     pc=pc)
                return x, nc, _zero_aux()
            if trem != "none":
                policy = {"minimal":
                          jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                          "full": jax.checkpoint_policies.nothing_saveable}[trem]
                inner = jax.checkpoint(inner, policy=policy)

            def mamba_step(c, xs2):
                x = c
                lp, lc = xs2
                x, nc, _ = inner(x, lp, lc)
                return x, nc
            x, new_gc = jax.lax.scan(mamba_step, x, (gp, gc))

            # shared attention block, weights alternate over applications
            sel = jnp.mod(gi, nsb)
            sp = jax.tree.map(lambda w: w[sel], params["shared"])
            x, new_ac, a = _attn_block(sp, x, cfg, rope=rope, mode=mode,
                                       cache=(None if mode == "train"
                                              else ac),
                                       pos=pos, pc=pc, attn_impl=attn_impl,
                                       cache_update=cache_update)
            if ac_all is not None and new_ac is not None:
                ac_all = jax.tree.map(
                    lambda c, n: jax.lax.dynamic_update_index_in_dim(
                        c, n.astype(c.dtype), gi, axis=0), ac_all, new_ac)
            return (x, _combine_aux(aux_acc, a), ac_all), new_gc

        gc = cache["groups"] if cache is not None \
            else jnp.zeros((n_groups, ae, 0))
        ac_all0 = cache["shared_attn"] if cache is not None else None
        (x, aux, new_ac_all), new_gc = jax.lax.scan(
            group_body, (x, aux, ac_all0),
            (params["groups"], gc, jnp.arange(n_groups)))
        new_cache["groups"] = new_gc
        new_cache["shared_attn"] = new_ac_all

    if "rem" in params:
        def body(x, lp, lc):
            x, nc = _mamba_block(lp, x, cfg, mode=mode,
                                 cache=(None if mode == "train" else lc),
                                 pc=pc)
            return x, nc, _zero_aux()
        rc = cache["rem"] if cache is not None else _dummy_cache(
            cfg, b, mode, n=jax.tree.leaves(params["rem"])[0].shape[0])
        x, new_rc, _ = _scan_layers(body, x, params["rem"], rc, remat=trem)
        new_cache["rem"] = new_rc
    return x, (new_cache if mode != "train" else None), aux


# -- encoder-decoder (seamless) ----------------------------------------------


def encode(params, cfg: ModelConfig, src_frames, pc=None, remat="none"):
    """Encoder over (stubbed) frame embeddings -> (B, S_src, d)."""
    pc = pc or NullConstraints()
    x = src_frames.astype(jnp.dtype(cfg.dtype))
    pos = jnp.arange(x.shape[1])[None, :]
    x = x + _sinusoidal(pos, cfg.d_model).astype(x.dtype)
    x = pc.tokens(x)

    def body(x, lp, lc):
        return _attn_block(lp, x, cfg, rope=None, mode="train", cache=lc,
                           pos=None, pc=pc, attn_impl="masked",
                           bidirectional=True)
    n = jax.tree.leaves(params["encoder"]["layers"])[0].shape[0]
    x, _, _ = _scan_layers(body, x, params["encoder"]["layers"],
                           _dummy_cache(cfg, x.shape[0], "train", n=n),
                           remat=remat)
    return apply_norm(params["encoder"]["final_norm"], x, cfg)


def encdec_cross_caches(params, cfg: ModelConfig, enc_out):
    """Per-decoder-layer cross K/V, stacked: (L, B, S_src, KV, D)."""
    def one(lp):
        return cross_kv(lp["cross"], enc_out, cfg)
    return jax.lax.map(one, params["dec_layers"])


def _encdec_forward(params, x, cfg, *, mode, cache, pos, pc, extras,
                    attn_impl, remat, cache_update="onehot"):
    trem = remat if mode == "train" else "none"
    b = x.shape[0]
    if mode in ("train", "prefill"):
        enc_out = encode(params, cfg, extras["src_frames"], pc=pc,
                         remat=trem)
        cross_caches = encdec_cross_caches(params, cfg, enc_out)
    else:
        cross_caches = cache["cross"]

    def body(x, lp, lc):
        sc, cc = lc
        return _attn_block(lp, x, cfg, rope=None, mode=mode, cache=sc,
                           pos=pos, pc=pc, attn_impl=attn_impl,
                           cross_kv_cache=cc, cache_update=cache_update)

    n = jax.tree.leaves(params["dec_layers"])[0].shape[0]
    sc = cache["self"] if cache is not None else _dummy_cache(cfg, b, mode,
                                                              n=n)
    x, new_sc, aux = _scan_layers(body, x, params["dec_layers"],
                                  (sc, cross_caches), remat=trem)
    new_cache = None
    if mode != "train":
        new_cache = {"self": new_sc,
                     "cross": jax.tree.map(
                         lambda c: c.astype(jnp.bfloat16), cross_caches)
                     if mode == "prefill" else cache["cross"]}
    return x, new_cache, aux


# ==========================================================================
# Loss / steps (pure model level; the distributed step lives in repro.train)
# ==========================================================================


def loss_fn(params, cfg: ModelConfig, batch, *, pc=None, attn_impl="auto",
            remat="none"):
    """Next-token CE loss + aux.  batch: {"tokens", "labels", extras...}."""
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    logits, _, aux = forward(params, cfg, tokens=batch["tokens"],
                             mode="train", pc=pc, extras=extras,
                             attn_impl=attn_impl, remat=remat)
    with jax.named_scope("logits_loss"):
        mask = (batch["labels"] >= 0)
        labels = jnp.maximum(batch["labels"], 0)
        loss = cross_entropy(logits, labels, cfg, mask=mask)
    total = loss
    if cfg.moe is not None:
        total = total + 0.01 * aux["moe_aux_loss"] / max(cfg.num_layers, 1)
    metrics = {"loss": loss, **aux}
    return total, metrics


def init_model_params(cfg: ModelConfig, seed: int = 0):
    return init_params(model_specs(cfg), seed)


def abstract_model_params(cfg: ModelConfig):
    return abstract_params(model_specs(cfg))

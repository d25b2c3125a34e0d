"""Attention path equivalences: chunked/decode vs dense masked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.attention import (chunked_attention, full_attention,
                                    gqa_attention, mla_attention)
from repro.models.layers import rope_table
from repro.models.params import init_params
from repro.models.attention import attn_specs, mla_specs


def _qkv(rng, b, s, h, kv, d):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window", [0, 32])
@pytest.mark.parametrize("kv", [2, 8])
def test_chunked_matches_full(rng, kv, window):
    q, k, v = _qkv(rng, 2, 128, 8, kv, 16)
    want = full_attention(q, k, v, causal=True, window=window)
    got = chunked_attention(q, k, v, causal=True, window=window, chunk_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_chunked_matches_full_s512(rng):
    """A 512-long causal sequence in four key chunks."""
    q, k, v = _qkv(rng, 1, 512, 4, 4, 16)
    want = full_attention(q, k, v, causal=True)
    got = chunked_attention(q, k, v, causal=True, window=0, chunk_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_gqa_decode_matches_train(rng):
    """Token-by-token decode with a cache == teacher-forced attention."""
    cfg = get_config("granite-3-8b", smoke=True)
    params = init_params(attn_specs(cfg), seed=0)
    b, s = 2, 16
    x = 0.1 * jnp.asarray(rng.standard_normal((b, s, cfg.d_model)),
                          jnp.float32)
    cos, sin = rope_table(jnp.arange(s)[None], cfg.head_dim, cfg.rope_theta)
    want, _ = gqa_attention(params, x, cfg, rope=(cos, sin), mode="train")

    cache = {"k": jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim)),
             "v": jnp.zeros((b, s, cfg.num_kv_heads, cfg.head_dim))}
    outs = []
    for t in range(s):
        cos_t, sin_t = rope_table(jnp.arange(t, t + 1)[None], cfg.head_dim,
                                  cfg.rope_theta)
        y, cache = gqa_attention(params, x[:, t:t + 1], cfg,
                                 rope=(cos_t, sin_t), mode="decode",
                                 cache=cache, pos=jnp.int32(t))
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def test_swa_ring_buffer_decode(rng):
    """Ring-buffer SWA decode == full-cache SWA decode beyond the window."""
    cfg = get_config("mixtral-8x7b", smoke=True)   # sliding_window=16
    cfg.num_kv_heads = cfg.num_heads               # MHA for the unit test
    params = init_params(attn_specs(cfg), seed=0)
    b, s, w = 1, 48, cfg.sliding_window
    x = 0.1 * jnp.asarray(rng.standard_normal((b, s, cfg.d_model)),
                          jnp.float32)
    cos, sin = rope_table(jnp.arange(s)[None], cfg.head_dim, cfg.rope_theta)
    want, _ = gqa_attention(params, x, cfg, rope=(cos, sin), mode="train")

    ring = {"k": jnp.zeros((b, w, cfg.num_kv_heads, cfg.head_dim)),
            "v": jnp.zeros((b, w, cfg.num_kv_heads, cfg.head_dim))}
    outs = []
    for t in range(s):
        cos_t, sin_t = rope_table(jnp.arange(t, t + 1)[None], cfg.head_dim,
                                  cfg.rope_theta)
        y, ring = gqa_attention(params, x[:, t:t + 1], cfg,
                                rope=(cos_t, sin_t), mode="decode",
                                cache=ring, pos=jnp.int32(t))
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4,
                               atol=3e-4)


def test_mla_decode_matches_train(rng):
    """Weight-absorbed MLA decode == decompressed train-path attention."""
    cfg = get_config("deepseek-v2-236b", smoke=True)
    params = init_params(mla_specs(cfg), seed=0)
    b, s = 2, 12
    x = 0.1 * jnp.asarray(rng.standard_normal((b, s, cfg.d_model)),
                          jnp.float32)
    rd = cfg.mla.qk_rope_head_dim
    cos, sin = rope_table(jnp.arange(s)[None], rd, cfg.rope_theta)
    want, _ = mla_attention(params, x, cfg, rope=(cos, sin), mode="train")

    cache = {"ckv": jnp.zeros((b, s, cfg.mla.kv_lora_rank)),
             "krope": jnp.zeros((b, s, rd))}
    outs = []
    for t in range(s):
        cos_t, sin_t = rope_table(jnp.arange(t, t + 1)[None], rd,
                                  cfg.rope_theta)
        y, cache = mla_attention(params, x[:, t:t + 1], cfg,
                                 rope=(cos_t, sin_t), mode="decode",
                                 cache=cache, pos=jnp.int32(t))
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_moe_dispatch_invariants(rng):
    """Sort-based MoE dispatch: top-k mass conservation + capacity."""
    from repro.models.moe import apply_moe, capacity, moe_specs
    cfg = get_config("mixtral-8x7b", smoke=True)
    params = init_params(moe_specs(cfg), seed=0)
    b, s = 4, 16
    x = 0.1 * jnp.asarray(rng.standard_normal((b, s, cfg.d_model)),
                          jnp.float32)
    y, aux = apply_moe(params, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.all(jnp.isfinite(y)))
    assert 0.0 <= float(aux["moe_dropped_frac"]) < 0.5
    assert float(aux["moe_aux_loss"]) >= 1.0 - 1e-3   # >= 1 by Cauchy-Schwarz
    # capacity is lane-aligned and >= tokens*topk/experts
    cap = capacity(cfg, b * s)
    assert cap % 8 == 0
    assert cap * cfg.moe.num_experts >= b * s * cfg.moe.top_k


@pytest.mark.parametrize("impl,backend,devices,changes,seq,want", [
    ("auto", "tpu", 1, {}, 2048, "flash"),
    ("auto", "cpu", 1, {}, 2048, "masked"),             # CPU tests
    ("auto", "tpu", 4, {}, 2048, "masked"),             # sharded inputs
    ("auto", "tpu", 1, {"attn_logit_softcap": 30.0}, 2048, "masked"),
    ("auto", "tpu", 1, {"attention_type": "mla"}, 2048, "masked"),
    ("auto", "tpu", 1, {}, 2000, "masked"),             # off the tiling
    ("auto", "tpu", 1, {"head_dim": 112}, 2048, "masked"),
    ("auto", "tpu", 1, {"head_dim": 256}, 2048, "masked"),
    ("auto", "tpu", 1, {"sliding_window": 4096}, 2048, "flash"),
    ("masked", "tpu", 1, {}, 2048, "masked"),           # explicit choices
    ("flash", "cpu", 1, {}, 2048, "flash"),
    ("recursive", "tpu", 1, {}, 2048, ValueError),      # unknown names
    ("flsh", "cpu", 1, {}, 2048, ValueError),
])
def test_select_attn_impl(impl, backend, devices, changes, seq, want):
    """The train path's attention as a pure function of backend, mesh
    size, softcap, attention type and shape; an unknown name raises."""
    import dataclasses
    from repro.models.attention import select_attn_impl
    cfg = dataclasses.replace(get_config("granite-3-8b"), **changes)
    if want is ValueError:
        with pytest.raises(ValueError, match=impl):
            select_attn_impl(impl, cfg, seq, mesh_devices=devices,
                             backend=backend)
        return
    assert select_attn_impl(impl, cfg, seq, mesh_devices=devices,
                            backend=backend) == want


@pytest.mark.parametrize("backend,kernel", [("cpu", False), ("tpu", True)])
def test_default_train_step_attention(monkeypatch, backend, kernel):
    """The default train step runs full_attention on the CPU backend and
    the flash kernels on a TPU backend, for a shape they tile (traced only,
    so no chip is needed)."""
    import dataclasses
    from repro.configs import TrainConfig
    from repro.models.transformer import init_model_params
    from repro.train.step import make_train_step

    cfg = dataclasses.replace(get_config("granite-3-8b", smoke=True),
                              head_dim=128)
    params = init_model_params(cfg, seed=0)
    step, opt = make_train_step(cfg, TrainConfig())
    toks = jnp.zeros((1, 128), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    jaxpr = str(jax.make_jaxpr(step)(params, opt.init(params),
                                     {"tokens": toks, "labels": toks},
                                     jnp.int32(0)))
    assert ("pallas_call" in jaxpr) == kernel

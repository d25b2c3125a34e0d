"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode.

(This container is CPU-only; ``interpret=True`` executes the kernel body in
Python, which validates the block decomposition, masking and online-softmax
logic.  The Mosaic lowering path is exercised on real TPUs.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, ops, ref


def _r(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# -- flash attention ---------------------------------------------------------

SWEEP = [
    # b, h, kv, s, d, causal, window, dtype
    (2, 4, 4, 256, 64, True, 0, jnp.float32),
    (1, 8, 2, 256, 64, True, 0, jnp.float32),
    (2, 4, 2, 256, 32, True, 64, jnp.float32),
    (1, 2, 2, 128, 64, False, 0, jnp.float32),
    (1, 4, 1, 128, 128, True, 0, jnp.float32),       # MQA
    (1, 4, 4, 128, 64, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("b,h,kv,s,d,causal,window,dtype", SWEEP)
def test_flash_attention_allclose(rng, b, h, kv, s, d, causal, window,
                                  dtype):
    q = _r(rng, (b, s, h, d), dtype)
    k = _r(rng, (b, s, kv, d), dtype)
    v = _r(rng, (b, s, kv, d), dtype)
    got = ops.flash_attention_bshd(q, k, v, causal=causal, window=window,
                                   bq=64, bk=64, interpret=True)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_shape_invariance(rng):
    q = _r(rng, (1, 256, 4, 32))
    k = _r(rng, (1, 256, 2, 32))
    v = _r(rng, (1, 256, 2, 32))
    a = ops.flash_attention_bshd(q, k, v, bq=128, bk=128, interpret=True)
    b = ops.flash_attention_bshd(q, k, v, bq=32, bk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


GRAD_CASES = [
    # b, h, kv, s, d, causal, window, dtype, block
    (1, 4, 1, 128, 32, True, 0, jnp.float32, 32),      # G=4, S = 4 x block
    (1, 2, 2, 64, 32, True, 0, jnp.float32, 32),       # G=1
    (1, 4, 1, 128, 32, True, 0, jnp.bfloat16, 32),
    (1, 4, 2, 128, 32, True, 48, jnp.float32, 32),     # sliding window
    (1, 2, 2, 64, 32, False, 0, jnp.float32, 32),      # bidirectional
]


@pytest.mark.parametrize("b,h,kv,s,d,causal,window,dtype,block", GRAD_CASES)
def test_flash_attention_grad_allclose(rng, b, h, kv, s, d, causal, window,
                                       dtype, block):
    """jax.grad through the kernels' custom VJP == jax.grad of the dense
    reference, dQ, dK and dV (dK/dV summed over each KV head's group)."""
    import jax
    q = _r(rng, (b, h, s, d), dtype)
    k = _r(rng, (b, kv, s, d), dtype)
    v = _r(rng, (b, kv, s, d), dtype)
    w = _r(rng, (b, h, s, d), jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    got = jax.jit(jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, window=window, bq=block, bk=block,
        interpret=True)), argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: ref.attention_ref(
        q, k, v, causal=causal, window=window)), argnums=(0, 1, 2))(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    for g, w_ in zip(got, want):
        assert g.dtype == dtype
        g, w_ = np.asarray(g, np.float32), np.asarray(w_, np.float32)
        scale = max(1.0, float(np.abs(w_).max()))
        np.testing.assert_allclose(g, w_, rtol=tol, atol=tol * scale)


@pytest.mark.parametrize("s,block,causal,window,want", [
    (2048, 512, True, 0, 10),           # 4 x 4 tiles, the diagonal's kept
    (2048, 512, False, 0, 16),
    (2048, 512, True, 512, 7),          # diagonal + one below
    (2048, 256, True, 0, 36),
])
def test_flash_attention_live_blocks(s, block, causal, window, want):
    """The tiles the kernels compute, which their declared FLOPs count."""
    assert fa.live_blocks(s, block, block, causal=causal,
                          window=window) == want
    ce = fa.cost_estimate((2, 32, s, 128), 8, 2, causal=causal,
                          window=window, bq=block, bk=block, kernel="dkv")
    assert ce["flops"] == 4 * 2 * 128 * 2 * 32 * want * block * block


# -- rmsnorm -------------------------------------------------------------------


@pytest.mark.parametrize("shape,dtype", [
    ((4, 100, 512), jnp.float32),
    ((7, 384), jnp.float32),
    ((2, 64, 256), jnp.bfloat16),
])
def test_rmsnorm_allclose(rng, shape, dtype):
    x = _r(rng, shape, dtype)
    scale = _r(rng, (shape[-1],), jnp.float32)
    got = ops.fused_rmsnorm(x, scale, interpret=True)
    want = ref.rmsnorm_ref(x, scale)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol,
                               atol=tol)


# -- ssd -----------------------------------------------------------------------


@pytest.mark.parametrize("l,chunk,p,n", [(256, 64, 32, 16), (128, 128, 16, 8),
                                         (192, 64, 8, 4)])
def test_ssd_kernel_allclose(rng, l, chunk, p, n):
    b, h = 2, 3
    x = _r(rng, (b, l, h, p))
    a = -jnp.abs(_r(rng, (b, l, h))) * 0.1
    bm = _r(rng, (b, l, h, n))
    cm = _r(rng, (b, l, h, n))
    got = ops.ssd_chunked_kernel(x, a, bm, cm, chunk=chunk, interpret=True)
    want = ref.ssd_ref(x.transpose(0, 2, 1, 3), a.transpose(0, 2, 1),
                       bm.transpose(0, 2, 1, 3), cm.transpose(0, 2, 1, 3)
                       ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_ssd_kernel_strong_decay_stable(rng):
    b, h, l, p, n = 1, 1, 128, 8, 4
    x = _r(rng, (b, l, h, p))
    a = -jnp.abs(_r(rng, (b, l, h))) * 20.0     # brutal decay
    bm = _r(rng, (b, l, h, n))
    cm = _r(rng, (b, l, h, n))
    y = ops.ssd_chunked_kernel(x, a, bm, cm, chunk=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y)))


# -- model-level integration ---------------------------------------------------


def test_flash_impl_matches_masked_at_model_level(rng):
    """forward and value_and_grad(loss_fn) with attn_impl="flash" match
    attn_impl="masked" for a reduced dense config (kernel runs in interpret
    mode on CPU)."""
    import jax
    from repro.configs import get_config
    from repro.models.transformer import forward, init_model_params, loss_fn

    cfg = get_config("granite-3-8b", smoke=True)
    params = init_model_params(cfg, seed=0)
    toks = jax.random.randint(jax.random.key(0), (2, 32), 0, cfg.vocab_size)
    ref_logits, _, _ = forward(params, cfg, tokens=toks, mode="train",
                               attn_impl="masked")
    fl_logits, _, _ = forward(params, cfg, tokens=toks, mode="train",
                              attn_impl="flash")
    np.testing.assert_allclose(
        np.asarray(fl_logits, np.float32), np.asarray(ref_logits, np.float32),
        rtol=5e-2, atol=5e-2)   # bf16 activations

    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}

    def value_and_grad(impl):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, cfg, batch, attn_impl=impl,
                              remat="minimal")[0]))(params)

    (l_ref, g_ref), (l_fl, g_fl) = value_and_grad("masked"), \
        value_and_grad("flash")
    np.testing.assert_allclose(float(l_fl), float(l_ref), rtol=1e-3)
    flat_ref, flat_fl = jax.tree.leaves(g_ref), jax.tree.leaves(g_fl)
    assert len(flat_ref) == len(flat_fl)
    for a, b in zip(flat_fl, flat_ref):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # bf16 activations: each leaf within 5 % of its own scale
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=5e-2 * float(np.abs(b).max()) + 1e-6)

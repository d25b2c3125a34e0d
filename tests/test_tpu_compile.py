"""Compiles for a described TPU v5e (no chip needed): the Pallas kernels at
real widths, and the granite-3-8b train step that ``chip_smoke.py`` runs.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, kernels over the scoped VMEM limit, programs larger than
the chip's memory.  The topology is described inside a module fixture —
only one process at a time may load the TPU library, so it must never be
touched while a module is imported.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ShapeConfig, TrainConfig, get_config
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms
from repro.kernels import ssd
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.steps import train_input_specs
from repro.models.params import abstract_params
from repro.models.transformer import model_specs
from repro.train.loop import compiled_step_constants
from repro.train.optim import opt_state_specs
from repro.train.step import make_train_step

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_text(lowered) -> str:
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles(one_chip, dtype):
    """granite-3-8b attention: 32 query heads over 8 KV heads of 128."""
    q = _sds(one_chip, (1, 32, 2048, 128), dtype)
    kv = _sds(one_chip, (1, 8, 2048, 128), dtype)
    text = _kernel_text(fa.flash_attention.lower(q, kv, kv))
    # the walk reads the flops the kernel declares to the compiler
    want = fa.cost_estimate(q.shape, 8, jnp.dtype(dtype).itemsize)
    assert analyze_hlo(text)["per_device"]["flops"] == want["flops"]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_grad_compiles(one_chip, dtype):
    """The backward kernels at granite-3-8b widths (B=2, S=2048) within the
    scoped VMEM; the walk reads each kernel's declared causal FLOPs, so
    forward + dQ + dK/dV is what ``cost_estimate`` counts (plus XLA's own
    ``rowsum(dO * O)``)."""
    q = _sds(one_chip, (2, 32, 2048, 128), dtype)
    kv = _sds(one_chip, (2, 8, 2048, 128), dtype)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v).astype(jnp.float32).sum()
    text = _kernel_text(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv))
    for name in ("flash_attention_dq", "flash_attention_dkv"):
        assert f"/{name}/pallas_call" in text
    declared = sum(fa.cost_estimate(q.shape, 8, jnp.dtype(dtype).itemsize,
                                    kernel=k)["flops"]
                   for k in ("fwd", "dq", "dkv"))
    flops = analyze_hlo(text)["per_device"]["flops"]
    assert declared <= flops <= 1.01 * declared


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_compiles(one_chip, dtype):
    """d_model 4096; fp32 rows once overflowed the scoped VMEM."""
    _kernel_text(rms.rmsnorm.lower(_sds(one_chip, (4096, 4096), dtype),
                                   _sds(one_chip, (4096,), dtype)))


def test_ssd_scan_compiles(one_chip):
    """zamba2-7b SSD widths: head dim 64, state 64, chunk 128."""
    f32 = jnp.float32
    x = _sds(one_chip, (1, 8, 2048, 64), f32)
    a = _sds(one_chip, (1, 8, 2048), f32)
    bc = _sds(one_chip, (1, 8, 2048, 64), f32)
    _kernel_text(ssd.ssd_scan.lower(x, a, bc, bc, chunk=128))


@pytest.fixture(scope="module")
def granite_step(one_chip):
    """The smoke's monitored train step compiled for one v5e, by the backend
    its attention selection sees: ``"cpu"`` runs ``full_attention``,
    ``"tpu"`` the flash kernels (``models.attention.select_attn_impl``)."""
    cfg = dataclasses.replace(get_config("granite-3-8b"), num_layers=2)
    tcfg = TrainConfig()
    shape = ShapeConfig("smoke", seq_len=2048, global_batch=2, kind="train")

    def sds(tree):
        return jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                            abstract_params(tree))

    pspecs = model_specs(cfg)
    args = (sds(pspecs), sds(opt_state_specs(pspecs, tcfg)),
            sds(train_input_specs(cfg, shape)),
            _sds(one_chip, (), jnp.int32))
    cache = {}

    def compiled(backend):
        if backend not in cache:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: backend)
                step, _ = make_train_step(cfg, tcfg)
                cache[backend] = jax.jit(step, donate_argnums=(0, 1)).lower(
                    *args).compile()
        return cache[backend]
    return compiled


def test_granite_train_step_fits_one_chip(granite_step):
    """The smoke's monitored train step: granite-3-8b at published widths,
    2 layers, B=2, S=2048, fp32 params + AdamW — arguments plus
    temporaries within one v5e's 16 GiB."""
    compiled = granite_step("cpu")
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < V5E_HBM_BYTES, used
    # the HPM constants count both scanned layers; XLA's cost analysis
    # counts the layer body once
    consts = compiled_step_constants(compiled, model_flops=1.0,
                                     tokens_per_step=1.0)
    assert consts["hlo_flops"] > 1.4 * compiled.cost_analysis()["flops"]


def test_granite_train_step_flash_kernels(granite_step):
    """On the chip the default step takes the flash kernels, under the
    ``attention`` scope the step's device-time shares read; it holds less
    than the masked step, and its HPM FLOPs drop by no more than the
    masked half of the attention products the masked step computes."""
    import re
    masked, flash = granite_step("cpu"), granite_step("tpu")
    text = flash.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'op_name="([^"]*)"', text)
    assert {n.split("/")[-2] for n in names} == {
        "flash_attention", "flash_attention_dq", "flash_attention_dkv"}
    assert all("/attention/" in n for n in names), names

    def temp(c):
        return c.memory_analysis().temp_size_in_bytes
    assert temp(flash) < temp(masked) - 2 ** 30

    def flops(c):
        return compiled_step_constants(c, model_flops=1.0,
                                       tokens_per_step=1.0)["hlo_flops"]
    b, h, s, d, layers = 2, 32, 2048, 128, 2
    # QK^T, PV forward and their four backward products, half masked
    masked_half = 3 * 2 * b * h * s * s * d * layers
    assert 0 < flops(masked) - flops(flash) <= masked_half


@pytest.mark.parametrize("d", [256, 512])
def test_step_constants_count_every_scanned_layer(one_chip, d):
    """The TPU backend lowers every matmul to a convolution and leaves the
    scan loop without ``known_trip_count``: the HPM step constants still
    add one layer's flops and bytes per scanned layer, so 2 -> 4 layers
    adds twice what 2 -> 3 does."""
    def consts(layers):
        def f(x, ws):
            def layer(h, w):
                return jnp.tanh(h @ w), None
            return jax.lax.scan(layer, x, ws)[0].sum()
        compiled = jax.jit(f).lower(
            _sds(one_chip, (8, d), jnp.float32),
            _sds(one_chip, (layers, d, d), jnp.float32)).compile()
        return compiled_step_constants(compiled, model_flops=1.0,
                                       tokens_per_step=1.0)

    c = {n: consts(n) for n in (1, 2, 3, 4)}
    flops = {n: c[n]["hlo_flops"] for n in c}
    per_layer = flops[3] - flops[2]
    assert 2 * 8 * d * d <= per_layer <= 1.05 * 2 * 8 * d * d
    assert flops[2] - flops[1] == pytest.approx(per_layer, rel=1e-3)
    assert flops[4] - flops[2] == pytest.approx(2 * per_layer, rel=1e-6)
    hbm = {n: c[n]["hlo_bytes"] for n in c}
    per_layer = hbm[3] - hbm[2]
    assert hbm[4] - hbm[2] == pytest.approx(2 * per_layer, rel=1e-6)
    # the layer's (bf16) weights at least once, and not the whole stack
    assert 2 * d * d <= per_layer <= 4 * 4 * d * d

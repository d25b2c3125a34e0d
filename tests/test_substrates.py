"""Optimizers, compression, checkpointing, data pipeline, sharding rules."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:     # minimal images: property tests skip, rest run
    from _hypothesis_compat import given, settings, st

from repro.ckpt import CheckpointManager, load_checkpoint, save_checkpoint
from repro.ckpt.checkpoint import available_steps
from repro.configs import TrainConfig
from repro.data import DataLoader, SyntheticTokenSource, make_batch_fn
from repro.configs.base import ShapeConfig
from repro.models.params import spec
from repro.parallel.sharding import (SERVE_RULES, TRAIN_RULES,
                                     logical_to_pspec, shardings_for_specs)
from repro.train.compression import (dequantize_int8, quantize_int8,
                                     quantization_error)
from repro.train.optim import (adafactor, adamw, clip_by_global_norm,
                               global_norm, lr_schedule, opt_state_specs)

# -- optimizers ---------------------------------------------------------------


def _quadratic_steps(opt, steps=120):
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    for i in range(steps):
        grads = {"w": 2 * (params["w"] - target)}
        params, state = opt.update(grads, state, params, 0.05)
    return float(jnp.max(jnp.abs(params["w"] - target)))


def test_adamw_converges():
    cfg = TrainConfig(weight_decay=0.0)
    assert _quadratic_steps(adamw(cfg)) < 0.1


def test_adafactor_converges():
    cfg = TrainConfig(weight_decay=0.0)
    assert _quadratic_steps(adafactor(cfg), steps=300) < 0.15


def test_adafactor_factored_state_small():
    cfg = TrainConfig(optimizer="adafactor")
    opt = adafactor(cfg)
    params = {"w": jnp.zeros((64, 128))}
    state = opt.init(params)
    s = state["s"]["w"]
    assert s["vr"].shape == (64,) and s["vc"].shape == (128,)
    assert s["m"].dtype == jnp.bfloat16     # bf16 momentum


def test_clip_by_global_norm():
    tree = {"a": jnp.ones(4) * 10.0}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(norm) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_lr_schedule_shape():
    cfg = TrainConfig(learning_rate=1e-3, warmup_steps=10, total_steps=100)
    lr = lr_schedule(cfg)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-2)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-2)
    assert float(lr(55)) < float(lr(12))


def test_opt_state_specs_match_init():
    """Spec-level opt state must structurally match the runtime opt state."""
    for name in ("adamw", "adafactor"):
        cfg = TrainConfig(optimizer=name)
        pspecs = {"w": spec((8, 16), ("embed", "mlp")),
                  "b": spec((16,), ("mlp",))}
        from repro.models.params import abstract_params, init_params
        params = init_params(pspecs)
        from repro.train.optim import get_optimizer
        state = get_optimizer(cfg).init(params)
        sspecs = abstract_params(opt_state_specs(pspecs, cfg))
        got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), state)
        want = jax.tree.map(lambda x: (x.shape, str(x.dtype)), sspecs)
        assert got == want, name


# -- gradient compression ------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(1e-6, 1e4))
def test_int8_quantization_error_bound(seed, scale):
    """|dequant(quant(x)) - x| <= scale_row / 2 elementwise (round-to-nearest
    symmetric int8)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((4, 64)) * scale, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    bound = np.asarray(s) / 2 + 1e-7 * scale
    assert (err <= bound + 1e-12).all()
    assert q.dtype == jnp.int8


def test_quantization_error_helper():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((32, 32)),
                    jnp.float32)
    e = quantization_error(x)
    assert float(jnp.max(jnp.abs(e))) < float(jnp.max(jnp.abs(x))) / 100


# -- checkpointing --------------------------------------------------------------


def _trees(v=1.0):
    return {"params": {"w": jnp.full((4, 4), v), "b": jnp.zeros(4)},
            "opt_state": {"m": jnp.full((4, 4), v / 2),
                          "count": jnp.int32(3)}}


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    save_checkpoint(d, 10, _trees(2.0), {"arch": "t"})
    step, out = load_checkpoint(d, _trees())
    assert step == 10
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]),
                                  np.full((4, 4), 2.0))
    assert int(out["opt_state"]["count"]) == 3


def test_checkpoint_atomicity(tmp_path):
    """A partial .tmp dir must never be visible as a checkpoint."""
    d = str(tmp_path / "ck")
    save_checkpoint(d, 1, _trees())
    os.makedirs(os.path.join(d, ".tmp-2"))          # simulated crash mid-save
    with open(os.path.join(d, ".tmp-2", "params.npz"), "w") as f:
        f.write("garbage")
    assert available_steps(d) == [1]
    step, _ = load_checkpoint(d, _trees())
    assert step == 1


def test_checkpoint_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=2, async_write=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _trees(float(s)))
    assert available_steps(str(tmp_path / "ck")) == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "ck"), keep=3, async_write=True)
    mgr.save(5, _trees(5.0))
    mgr.wait()
    step, out = mgr.restore(_trees())
    assert step == 5 and float(out["params"]["w"][0, 0]) == 5.0


def test_checkpoint_elastic_reshard(tmp_path):
    """Load with explicit (single-device) shardings — the elastic path."""
    d = str(tmp_path / "ck")
    save_checkpoint(d, 7, _trees(3.0))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), _trees()["params"])
    step, out = load_checkpoint(d, {"params": _trees()["params"]},
                                shardings={"params": sh})
    assert out["params"]["w"].sharding == NamedSharding(mesh, P())


# -- data pipeline ----------------------------------------------------------------


def test_synthetic_determinism():
    s1 = SyntheticTokenSource(1000, seed=3)
    s2 = SyntheticTokenSource(1000, seed=3)
    np.testing.assert_array_equal(s1.batch(5, 4, 16), s2.batch(5, 4, 16))
    assert not np.array_equal(s1.batch(5, 4, 16), s1.batch(6, 4, 16))
    assert s1.batch(0, 4, 16).max() < 1000


def test_host_sharded_loader():
    src = SyntheticTokenSource(100, seed=0)
    shape = ShapeConfig("t", seq_len=8, global_batch=8, kind="train")
    fn = make_batch_fn(src, None, shape)
    full = fn(0, slice(0, 8))
    loaders = [DataLoader(fn, host_index=i, host_count=2, global_batch=8)
               for i in range(2)]
    try:
        got = {}
        for i, ld in enumerate(loaders):
            step, b = next(ld)
            assert step == 0
            assert b["tokens"].shape == (4, 8)
            got[i] = b["tokens"]
        np.testing.assert_array_equal(
            np.concatenate([got[0], got[1]]), full["tokens"])
    finally:
        for ld in loaders:
            ld.close()


def test_loader_replay_from_step():
    src = SyntheticTokenSource(100, seed=0)
    shape = ShapeConfig("t", seq_len=8, global_batch=4, kind="train")
    fn = make_batch_fn(src, None, shape)
    ld = DataLoader(fn, global_batch=4, start_step=17)
    try:
        step, b = next(ld)
        assert step == 17
        np.testing.assert_array_equal(b["tokens"], fn(17, slice(0, 4))["tokens"])
    finally:
        ld.close()


# -- sharding rules ------------------------------------------------------------------

from jax.sharding import PartitionSpec as P  # noqa: E402


@pytest.fixture(scope="module")
def mesh2x2():
    dev = np.array(jax.devices() * 4).reshape(2, 2)
    from jax.sharding import Mesh
    return Mesh(dev, ("data", "model"))


def test_pspec_basic(mesh2x2):
    ps = logical_to_pspec(("embed", "mlp"), (8, 16), TRAIN_RULES, mesh2x2)
    assert ps == P("data", "model")


def test_pspec_divisibility_fallback(mesh2x2):
    # 7 % 2 != 0 -> replicate that dim, keep the other
    ps = logical_to_pspec(("embed", "kv_heads"), (8, 7), TRAIN_RULES,
                          mesh2x2)
    assert ps == P("data")
    ps = logical_to_pspec(("embed", "heads"), (7, 8), TRAIN_RULES, mesh2x2)
    assert ps == P(None, "model")


def test_pspec_axis_used_once(mesh2x2):
    # both "heads" and "mlp" want "model"; only the first (priority order)
    ps = logical_to_pspec(("heads", "mlp"), (8, 8), TRAIN_RULES, mesh2x2)
    assert ps == P("model")


def test_pspec_cache_priority(mesh2x2):
    # kv_heads divisible -> it wins the model axis, cache_seq replicated
    ps = logical_to_pspec(("batch", "cache_seq", "kv_heads", None),
                          (8, 64, 4, 16), SERVE_RULES, mesh2x2)
    assert ps == P("data", None, "model")
    # kv_heads NOT divisible -> cache_seq takes the model axis
    ps = logical_to_pspec(("batch", "cache_seq", "kv_heads", None),
                          (8, 64, 3, 16), SERVE_RULES, mesh2x2)
    assert ps == P("data", "model")


def test_pspec_multi_axis_batch():
    from jax.sharding import Mesh
    dev = np.array(jax.devices() * 8).reshape(2, 2, 2)
    mesh = Mesh(dev, ("pod", "data", "model"))
    ps = logical_to_pspec(("batch", "seq"), (8, 32), TRAIN_RULES, mesh)
    assert ps == P(("pod", "data"))


def test_shardings_for_specs_tree(mesh2x2):
    tree = {"w": spec((8, 16), ("embed", "mlp")),
            "scale": spec((16,), ("norm",))}
    sh = shardings_for_specs(tree, TRAIN_RULES, mesh2x2)
    assert sh["w"].spec == P("data", "model")
    assert sh["scale"].spec == P()

"""Launcher path: bundles lower+compile on a 1x1 mesh (smoke configs) and
the mesh/config helpers."""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, ShapeConfig, TrainConfig, get_config
from repro.launch.mesh import make_mesh, make_mesh_for
from repro.launch.steps import (build_bundle, build_decode_bundle,
                                build_prefill_bundle, build_train_bundle,
                                input_specs, lower_bundle)

TINY = ShapeConfig("tiny", seq_len=32, global_batch=2, kind="train")
TINY_PREFILL = ShapeConfig("tinyp", seq_len=32, global_batch=2,
                           kind="prefill")
TINY_DECODE = ShapeConfig("tinyd", seq_len=32, global_batch=2, kind="decode")


@pytest.fixture(scope="module")
def mesh1():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", ["granite-3-8b", "mixtral-8x7b",
                                  "rwkv6-1.6b", "zamba2-7b",
                                  "deepseek-v2-236b",
                                  "seamless-m4t-large-v2", "qwen2-vl-7b"])
def test_bundles_lower_and_compile(arch, mesh1):
    """Every bundle kind lowers AND compiles for a reduced config."""
    cfg = get_config(arch, smoke=True)
    for shape in (TINY, TINY_PREFILL, TINY_DECODE):
        bundle = build_bundle(cfg, shape, mesh1,
                              train_cfg=TrainConfig(num_microbatches=2))
        compiled = lower_bundle(bundle, mesh1).compile()
        assert compiled.cost_analysis()["flops"] > 0
        mem = compiled.memory_analysis()
        assert mem.temp_size_in_bytes >= 0


def test_input_specs_cover_modalities():
    cfg = get_config("qwen2-vl-7b")
    sp = input_specs(cfg, SHAPES["prefill_32k"])
    assert {"tokens", "patches", "mrope_pos"} <= set(sp)
    sp = input_specs(cfg, SHAPES["decode_32k"])
    assert "patches" not in sp and "mrope_pos" in sp
    cfg = get_config("seamless-m4t-large-v2")
    sp = input_specs(cfg, SHAPES["train_4k"])
    assert "src_frames" in sp
    assert sp["tokens"].shape == (256, 4096)


def test_make_mesh_for_elastic():
    m = make_mesh_for(1)
    assert m.devices.size == 1
    assert m.axis_names == ("data", "model")


def test_make_mesh_axes_are_auto():
    """with_sharding_constraint accepts Auto axes only."""
    from jax.sharding import AxisType
    assert make_mesh((1, 1), ("data", "model")).axis_types == \
        (AxisType.Auto, AxisType.Auto)
    assert set(make_mesh_for(1).axis_types) == {AxisType.Auto}


_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_compilation_cache_include_metadata_in_key",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")


def test_compile_cache_placement(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is left to JAX; otherwise the
    cache is the fixed <repo>/.jax_cache.  Either way its keys include
    the program's metadata."""
    from repro.launch.compile_cache import (REPO_CACHE_DIR,
                                            enable_compile_cache)
    prev = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == \
            prev["jax_compilation_cache_dir"]
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


def test_cached_executable_keeps_its_own_scopes(monkeypatch, tmp_path):
    """Two builds of one HLO that differ only in a named scope, sharing a
    cache directory: each compiled step carries its own scope, so a
    profile of either attributes device time to that build's scopes."""
    import jax.numpy as jnp
    from jax._src import compilation_cache
    from repro.launch.compile_cache import enable_compile_cache

    def build(scope):
        def step(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x)
        return jax.jit(step)

    prev = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
        # JAX reads the variable at start-up, before this test set it
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        x = jnp.ones((8, 8))
        first = build("parent_scope").lower(x).compile().as_text()
        second = build("change_scope").lower(x).compile().as_text()
        assert "parent_scope" in first
        assert "change_scope" in second and "parent_scope" not in second
        assert len(list(tmp_path.glob("jit_step-*"))) == 2
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

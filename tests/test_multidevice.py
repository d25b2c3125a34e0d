"""Multi-device behaviours (8 forced host devices, subprocess-isolated:
the main test process must keep seeing 1 device per the assignment)."""

import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pjit_train_step_on_mesh():
    """Smoke config train step under pjit on a 4x2 mesh with the production
    rule table: loss decreases and params stay sharded."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, TrainConfig, ShapeConfig
        from repro.launch.mesh import make_mesh_for
        from repro.launch.steps import build_train_bundle
        from repro.models.transformer import init_model_params, model_specs
        from repro.train.optim import get_optimizer
        from repro.parallel.sharding import shardings_for_specs, TRAIN_RULES
        from repro.data import SyntheticTokenSource

        cfg = get_config("lms-demo", smoke=True)
        tcfg = TrainConfig(num_microbatches=2, learning_rate=5e-3,
                           warmup_steps=1)
        shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
        mesh = make_mesh_for(8, model=2)
        assert mesh.devices.shape == (4, 2)

        bundle = build_train_bundle(cfg, shape, tcfg, mesh)
        params = init_model_params(cfg, 0)
        opt = get_optimizer(tcfg)
        opt_state = opt.init(params)
        psh = shardings_for_specs(model_specs(cfg), TRAIN_RULES, mesh)
        params = jax.device_put(params, psh)

        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       donate_argnums=(0, 1))
        src = SyntheticTokenSource(cfg.vocab_size, seed=0)
        losses = []
        with mesh:
            for i in range(6):
                t = src.batch(i, 8, 32)
                batch = {"tokens": jnp.asarray(t[:, :-1]),
                         "labels": jnp.asarray(t[:, 1:])}
                params, opt_state, m = step(params, opt_state, batch,
                                            jnp.int32(i))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses
        emb = params["embed"]["embedding"]
        assert len(emb.sharding.device_set) == 8
        print("LOSSES", [round(x, 3) for x in losses])
    """)
    assert "LOSSES" in out


def test_compressed_pmean_shard_map():
    """int8 compressed all-reduce over a pure-DP axis == exact mean (within
    quantization tolerance)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        from repro.train.compression import compressed_pmean

        mesh = make_mesh((8,), ("pod",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 4, 16)),
                        jnp.float32)

        def f(xs):
            return compressed_pmean({"g": xs[0]}, "pod", "int8")["g"]

        got = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("pod"),),
                                    out_specs=P(None),
                                    check_vma=False))(x)
        want = jnp.mean(x, axis=0)
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(x))) / 127
        assert err <= scale, (err, scale)
    """)


def test_cross_pod_compressed_train_step():
    """Full train step with hierarchical pod-axis int8 gradient sync (manual
    pod axis + auto data/model axes) compiles and runs."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, TrainConfig, ShapeConfig
        from repro.launch.mesh import make_mesh
        from repro.train.step import make_train_step
        from repro.train.optim import get_optimizer
        from repro.models.transformer import init_model_params
        from repro.parallel.sharding import (PartitionConstraints,
                                             TRAIN_RULES)

        cfg = get_config("lms-demo", smoke=True)
        tcfg = TrainConfig(grad_compression="int8", learning_rate=1e-3,
                           warmup_steps=1)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        # inside the manual-pod region the constraints must not name "pod"
        pc = PartitionConstraints(TRAIN_RULES.with_overrides(
            batch=("data",)), mesh)
        step, _ = make_train_step(cfg, tcfg, pc=pc, mesh=mesh)
        params = init_model_params(cfg, 0)
        opt_state = get_optimizer(tcfg).init(params)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        with mesh:
            p2, o2, m = jax.jit(step)(params, opt_state, batch,
                                      jnp.int32(0))
        assert jnp.isfinite(m["loss"])
        # compressed path really lowered an int8 all-gather over the pod axis
        txt = jax.jit(step).lower(params, opt_state, batch,
                                  jnp.int32(0)).compile().as_text()
        assert "s8" in txt and "all-gather" in txt, "int8 exchange missing"
        print("OK", float(m["loss"]))
    """)


def test_elastic_restart_smaller_mesh(tmp_path):
    """Checkpoint on a 4x2 mesh, restore onto 2x2 (elastic reshard)."""
    _run(f"""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models.transformer import init_model_params, model_specs
        from repro.parallel.sharding import shardings_for_specs, TRAIN_RULES
        from repro.ckpt import save_checkpoint, load_checkpoint
        from repro.launch.mesh import make_mesh, make_mesh_for

        cfg = get_config("lms-demo", smoke=True)
        params = init_model_params(cfg, 0)
        mesh8 = make_mesh_for(8, model=2)
        sh8 = shardings_for_specs(model_specs(cfg), TRAIN_RULES, mesh8)
        params = jax.device_put(params, sh8)
        save_checkpoint({str(tmp_path)!r}, 3, {{"params": params}})

        # "failure": restart with only 4 devices
        mesh4 = make_mesh((2, 2), ("data", "model"),
                          devices=jax.devices()[:4])
        sh4 = shardings_for_specs(model_specs(cfg), TRAIN_RULES, mesh4)
        step, out = load_checkpoint({str(tmp_path)!r},
                                    {{"params": params}},
                                    shardings={{"params": sh4}})
        emb = out["params"]["embed"]["embedding"]
        assert step == 3
        assert len(emb.sharding.device_set) == 4
        print("ELASTIC OK")
    """)


def test_compiled_step_constants_sharded_collectives():
    """Regression (marker PR satellite): the seed's train loop hardcoded
    collective_bytes=0.0 into the HPM step constants.  A model-sharded
    step compiles all-reduces/all-gathers; compiled_step_constants must
    surface their operand and wire bytes from the HLO walk."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, TrainConfig, ShapeConfig
        from repro.launch.mesh import make_mesh_for
        from repro.launch.steps import build_train_bundle
        from repro.models.transformer import init_model_params, model_specs
        from repro.parallel.sharding import shardings_for_specs, TRAIN_RULES
        from repro.train.optim import get_optimizer
        from repro.train.loop import compiled_step_constants
        from repro.data import SyntheticTokenSource

        cfg = get_config("lms-demo", smoke=True)
        tcfg = TrainConfig(learning_rate=5e-3, warmup_steps=1)
        shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
        mesh = make_mesh_for(8, model=2)
        bundle = build_train_bundle(cfg, shape, tcfg, mesh)
        params = init_model_params(cfg, 0)
        opt = get_optimizer(tcfg)
        opt_state = opt.init(params)
        psh = shardings_for_specs(model_specs(cfg), TRAIN_RULES, mesh)
        params = jax.device_put(params, psh)
        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings)
        t = SyntheticTokenSource(cfg.vocab_size, seed=0).batch(0, 8, 32)
        batch = {"tokens": jnp.asarray(t[:, :-1]),
                 "labels": jnp.asarray(t[:, 1:])}
        with mesh:
            compiled = step.lower(params, opt_state, batch,
                                  jnp.int32(0)).compile()
        consts = compiled_step_constants(compiled, model_flops=1.0,
                                         tokens_per_step=8 * 32)
        assert consts["hlo_flops"] > 0
        assert consts["collective_bytes"] > 0, consts
        assert consts["wire_bytes"] > 0, consts
        print("COLLECTIVE_BYTES", consts["collective_bytes"],
              consts["wire_bytes"])
    """)
    assert "COLLECTIVE_BYTES" in out

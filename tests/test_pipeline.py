"""Pipeline parallelism: pipelined == sequential, on an 8-device host mesh
(subprocess-isolated like test_multidevice)."""

import os
import subprocess
import sys
import textwrap

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


def test_pipeline_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_apply, bubble_fraction

        mesh = make_mesh((4,), ("pipe",))
        S, B, D = 4, 8, 16
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

        def stage(w, xb):
            return jnp.tanh(xb @ w)

        got = pipeline_apply(stage, ws, x, mesh=mesh, num_microbatches=4)

        want = x
        for s in range(S):
            want = stage(ws[s], want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9

        # collective-permute must appear in the lowered HLO (neighbor links)
        txt = jax.jit(lambda w, x: pipeline_apply(
            stage, w, x, mesh=mesh, num_microbatches=4)
        ).lower(ws, x).compile().as_text()
        assert "collective-permute" in txt
        print("PIPELINE OK")
    """)
    assert "PIPELINE OK" in out


def test_pipeline_composes_with_data_axis():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import pipeline_apply

        mesh = make_mesh((2, 4), ("data", "pipe"))
        S, B, D = 4, 8, 16
        rng = np.random.default_rng(1)
        ws = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

        def stage(w, xb):
            return jnp.tanh(xb @ w)

        f = jax.jit(lambda w, x: pipeline_apply(
            stage, w, x, mesh=mesh, num_microbatches=2))
        with mesh:
            got = f(ws, x)
        want = x
        for s in range(S):
            want = stage(ws[s], want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("PIPELINE+DATA OK")
    """)
    assert "PIPELINE+DATA OK" in out

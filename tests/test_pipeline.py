"""GPipe pipeline (stage axis) vs sequential oracle — subprocess, 4 devices."""

import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_gpipe_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import gpipe, sequential_reference
        from repro.parallel.sharding import make_mesh

        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(0)
        d = 16
        params = {"w": jnp.asarray(rng.standard_normal((4, d, d)) * 0.3,
                                   jnp.float32),
                  "b": jnp.asarray(rng.standard_normal((4, d)) * 0.1,
                                   jnp.float32)}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        x = jnp.asarray(rng.standard_normal((6, 8, d)), jnp.float32)
        piped = jax.jit(gpipe(stage_fn, mesh))(params, x)
        ref = sequential_reference(stage_fn, params, x)
        err = float(jnp.max(jnp.abs(piped - ref)))
        print("err", err)
        assert err < 1e-5
        print("PIPELINE_OK")
    """)
    assert "PIPELINE_OK" in out


def test_gpipe_differentiable():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import gpipe, sequential_reference
        from repro.parallel.sharding import make_mesh

        mesh = make_mesh((4,), ("stage",))
        rng = np.random.default_rng(1)
        d = 8
        params = {"w": jnp.asarray(rng.standard_normal((4, d, d)) * 0.3,
                                   jnp.float32)}

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])

        x = jnp.asarray(rng.standard_normal((5, 4, d)), jnp.float32)
        piped = gpipe(stage_fn, mesh)
        g1 = jax.grad(lambda p: jnp.sum(piped(p, x) ** 2))(params)
        g2 = jax.grad(lambda p: jnp.sum(
            sequential_reference(stage_fn, p, x) ** 2))(params)
        err = float(jnp.max(jnp.abs(g1["w"] - g2["w"])))
        print("grad err", err)
        assert err < 1e-4
        print("PIPELINE_GRAD_OK")
    """)
    assert "PIPELINE_GRAD_OK" in out

"""Interpret-mode resolution: env-driven off the TPU, never on it.

``repro.kernels.interpret_default`` resolves per call: on a TPU backend the
kernels always compile and ``REPRO_PALLAS_INTERPRET=1`` is an error; on any
other backend the env var overrides and the default is the interpreter.
The TPU branch is driven here by patching ``jax.default_backend``.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels.ocs_quant import ops as q_ops


@pytest.mark.parametrize("value,expect", [
    ("1", True), ("true", True), ("YES", True), ("on", True),
    ("0", False), ("false", False), ("No", False), ("off", False),
    (" 1 ", True),
])
def test_env_override_resolves_both_settings(monkeypatch, value, expect):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    assert kernels.interpret_default() is expect


@pytest.mark.parametrize("value,expect", [
    (None, False), ("0", False), ("off", False),
    ("1", RuntimeError), ("true", RuntimeError), ("maybe", ValueError),
])
def test_tpu_backend_never_interprets(monkeypatch, value, expect):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if value is None:
        monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    else:
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", value)
    if expect is False:
        assert kernels.interpret_default() is False
    else:
        with pytest.raises(expect):
            kernels.interpret_default()


def test_invalid_env_value_raises(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "maybe")
    with pytest.raises(ValueError):
        kernels.interpret_default()


def test_default_follows_backend(monkeypatch):
    """Without the env var, CPU/GPU hosts interpret and a TPU backend
    compiles (asserted via the same code path the wrappers call)."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    import jax
    assert kernels.interpret_default() is (jax.default_backend() != "tpu")


def test_wrappers_read_resolution_at_call_time(monkeypatch):
    """Flipping the env var takes effect without re-import: with interpret
    forced on, the wrapped kernels still run (this host has no TPU, so the
    hardcoded-False failure mode would raise at lowering)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    x = jnp.linspace(-2.0, 2.0, 64 * 64, dtype=jnp.float32).reshape(64, 64)
    codes = q_ops.encode(x, 8)
    assert codes.dtype == jnp.uint8
    from repro.core import quantize as qz
    assert np.array_equal(np.asarray(codes), np.asarray(qz.quantize(x, 8)))

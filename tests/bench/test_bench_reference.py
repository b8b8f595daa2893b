"""The plain channel reference meets the program's contention exactly: same
codes, same sensing stream, same winners, for a whole frame and for the
columns of one slot read out of it."""

import bench_tiny  # noqa: F401  (puts the repository on the path)
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import ocs_ref
from repro.core import ocs, quantize


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("p_miss", [0.0, 0.05, 0.3])
def test_winners_match_the_program(dtype, p_miss):
    n, k = 8, 96
    h = jax.random.normal(jax.random.PRNGKey(1), (n, k), jnp.float32)
    h = h.astype(dtype)
    key = jax.random.PRNGKey(7)
    want = ocs.ocs_maxpool_noisy(h, key, bits=8, p_miss=p_miss,
                                 max_rounds=3).winner
    p_keep = ocs.sensing_keep_prob(p_miss, dtype)
    code = ocs_ref.codes(h.astype(jnp.float32), 8)
    np.testing.assert_array_equal(code, quantize.quantize(h, 8))
    got = ocs_ref.contend(code, key, p_keep, 8, 3)
    np.testing.assert_array_equal(got, want)
    # one slot's columns, with the draws made over the whole frame
    got_cols = ocs_ref.contend(code[:, 32:64], key, p_keep, 8, 3,
                               frame_k=k, col0=32)
    np.testing.assert_array_equal(got_cols, want[32:64])


@pytest.mark.parametrize("dtype,width", [(jnp.float32, 32),
                                         (jnp.bfloat16, 16)])
def test_dequantized_code_is_the_programs(dtype, width):
    h = jax.random.normal(jax.random.PRNGKey(3), (4096,), jnp.float32)
    code = quantize.quantize(h.astype(dtype), 8)
    want = quantize.dequantize(code, 8, dtype).astype(jnp.float32)
    np.testing.assert_array_equal(ocs_ref.dequant(code, 8, width), want)

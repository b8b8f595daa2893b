"""The Pallas kernels compile for a TPU v5e chip at the main paths' widths.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
described ``v5e:2x2`` topology that need not be attached, so a kernel the
chip would refuse fails here at no chip time.  The topology is described
inside a module-scoped fixture (never at import: only one process at a time
may load the TPU library), which skips where it cannot be described.  Every
kernel is called with ``interpret=False`` and its compiled text must hold
the Mosaic kernel (``tpu_custom_call``).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ocs
from repro.kernels.flash_attention import flash_attention as FA
from repro.kernels.maxpool import maxpool as MP
from repro.kernels.ocs_contention import ocs_contention as OC
from repro.kernels.ocs_quant import ocs_quant as OQ


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,k", [(4, 2048), (16, 8192)],
                         ids=["curve", "serve_tick"])
def test_contention_compiles(one_chip, n, k):
    bits, rounds = 8, 3
    n_slots = bits + ocs.host_id_bits(n)
    _compile(lambda w, h, m, tb: OC.contend(
        w, h, m, tb, n_slots=n_slots, max_rounds=rounds, interpret=False),
        one_chip, ((n, k), jnp.uint32), ((rounds, n, k), jnp.uint32),
        ((n,), jnp.bool_), ((), jnp.int32))


def test_flash_attention_forward_compiles(one_chip):
    shape = ((2, 16, 1024, 64), jnp.bfloat16)
    _compile(lambda q, k, v: FA.flash_attention(q, k, v, interpret=False),
             one_chip, shape, shape, shape)


def test_maxpool_forward_compiles(one_chip):
    _compile(lambda h: MP.maxpool_fused(h, interpret=False), one_chip,
             ((16, 512, 1024), jnp.float32))


def test_maxpool_backward_compiles(one_chip):
    _compile(lambda w, g: MP.maxpool_winner_bwd(w, g, 16, interpret=False),
             one_chip, ((512, 1024), jnp.int32), ((512, 1024), jnp.float32))


def test_ocs_quant_encode_compiles(one_chip):
    _compile(lambda x: OQ.encode(x, 8, interpret=False), one_chip,
             ((512, 1024), jnp.float32))


def test_ocs_quant_decode_compiles(one_chip):
    _compile(lambda c: OQ.decode(c, 8, jnp.float32, interpret=False),
             one_chip, ((512, 1024), jnp.uint8))

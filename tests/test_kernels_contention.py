"""Fused noisy-contention kernel: parity with the scan protocol core.

Three layers of evidence, all bit-for-bit:

  * kernel-level: ``ops.contend`` vs ``ref.contend`` on identical packed
    operands (the unified parity harness, masked workers included);
  * core-level: ``ocs_maxpool_noisy_core(backend="pallas")`` vs the
    ``lax.scan`` backend on (p_miss x bits x N incl. padded) grids — every
    ``NoisyOCSResult`` field, so winner selection AND the rounds / slots /
    collision accounting agree exactly;
  * model-level: ``fedocs.maxpool_noisy(backend="pallas")`` at ``p_miss=0``
    reduces to ``maxpool_quantized(tie_break="first")`` in forward and vjp,
    and under vmap lanes (the train-curve usage) matches the scan backend.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kernel_parity import ParityOp, check
from proptest import grid, random_floats, seeds, sweep
from repro.core import fedocs, ocs
from repro.kernels.ocs_contention import ops as O
from repro.kernels.ocs_contention import ref as R


# ---------------------------------------------------------------------------
# kernel-level parity on packed operands (unified harness)
# ---------------------------------------------------------------------------

def _contend_args(case):
    n, k, n_slots = case["n"], case["k"], case["n_slots"]
    rng = np.random.default_rng(case["seed"])
    # arbitrary contention words within the live bit budget
    word = jnp.asarray(
        rng.integers(0, 1 << case["total_bits"], (n, k), dtype=np.uint32))
    n_real = case.get("n_real", n)
    mask = jnp.arange(n) < n_real
    p_keep = ocs.sensing_keep_prob(case["p_miss"], jnp.float32)
    heard = O.draw_heard_packed(
        jax.random.PRNGKey(case["seed"]), p_keep, n, k,
        n_slots=n_slots, max_rounds=case["max_rounds"])
    return word, heard, mask, jnp.int32(case["total_bits"])


def _check_contend(cases):
    """Drive the unified harness; contend's static kwargs come per case."""
    def one(case):
        kw = dict(n_slots=case["n_slots"], max_rounds=case["max_rounds"])
        if "block_k" in case:
            kkw = dict(kw, block_k=case["block_k"])
        else:
            kkw = kw
        check(ParityOp(
            name="ocs_contention.contend",
            make=_contend_args,
            kernel=lambda *args, _kw=kkw: O.contend(*args, **_kw),
            reference=lambda *args, _kw=kw: R.contend(*args, **_kw),
            cases=[case],
        ))
    sweep(one, list(cases), label="contend")


def test_contend_parity_fast():
    _check_contend(grid(n=[4], k=[96], n_slots=[14], total_bits=[14],
                        max_rounds=[3], p_miss=[0.0, 0.2], seed=[0, 1]))


def test_contend_parity_masked_and_padded_slots():
    # padded workers (mask) + padded scan bound (total_bits < n_slots) +
    # a block size that forces multiple tiles (cross-tile accounting) with
    # K padded up to a whole last tile
    _check_contend(grid(n=[8], n_real=[5], k=[320], n_slots=[16],
                        total_bits=[14], max_rounds=[2], p_miss=[0.15],
                        seed=[0, 3], block_k=[128]))


def test_contend_rejects_unaligned_block():
    word = jnp.zeros((4, 256), jnp.uint32)
    heard = jnp.zeros((1, 4, 256), jnp.uint32)
    with pytest.raises(ValueError, match="multiple of 128"):
        O.contend(word, heard, jnp.ones((4,), bool), jnp.int32(8),
                  n_slots=8, max_rounds=1, block_k=96)


@pytest.mark.slow
def test_contend_parity_grid():
    _check_contend(grid(n=[2, 8, 16], k=[64, 160], n_slots=[10, 20],
                        total_bits=[10], max_rounds=[1, 3],
                        p_miss=[0.0, 0.05, 0.5, 0.97], seed=[0]))


# ---------------------------------------------------------------------------
# core-level parity: every NoisyOCSResult field, scan vs pallas
# ---------------------------------------------------------------------------

def _core_pair(h, mask, id_bits, key, p_miss, **kw):
    a = ocs.ocs_maxpool_noisy_core(h, mask, id_bits, key, p_miss,
                                   backend="scan", **kw)
    b = ocs.ocs_maxpool_noisy_core(h, mask, id_bits, key, p_miss,
                                   backend="pallas", **kw)
    return a, b


def _assert_results_equal(a, b, ctx=""):
    for f in dataclasses.fields(a):
        x, y = np.asarray(getattr(a, f.name)), np.asarray(getattr(b, f.name))
        assert x.dtype == y.dtype, f"{ctx}{f.name}: {x.dtype} != {y.dtype}"
        assert np.array_equal(x, y), f"{ctx}{f.name}: scan {x} != pallas {y}"


def test_core_backend_parity_fast():
    def prop(case):
        n, bits, p = case["n"], case["bits"], case["p_miss"]
        h = jnp.asarray(random_floats(case["seed"], (n, 48), specials=False))
        key = jax.random.PRNGKey(case["seed"])
        id_bits = ocs.host_id_bits(n)
        a, b = _core_pair(h, jnp.ones((n,), bool), id_bits, key, p,
                          bits=bits, max_id_bits=id_bits, max_rounds=3)
        _assert_results_equal(a, b, f"{case}: ")
    # p_miss=0 core coverage lives in the reduction tests below; the fast
    # tier exercises the re-contention path at one miss rate per shape
    sweep(prop, list(grid(n=[4, 9], bits=[8, 16], p_miss=[0.1],
                          seed=[0])), label="core")


def test_core_backend_parity_padded_workers():
    """Masked/padded rows + oversized scan bound: identical accounting."""
    h = jnp.asarray(random_floats(1, (6, 40), specials=False))
    mask = jnp.arange(16) < 6
    h_pad = jnp.zeros((16, 40), jnp.float32).at[:6].set(h).at[6:].set(1e9)
    id_bits = ocs.host_id_bits(6)
    a, b = _core_pair(h_pad, mask, id_bits, jax.random.PRNGKey(2), 0.12,
                      bits=12, max_id_bits=ocs.host_id_bits(16),
                      max_rounds=3)
    _assert_results_equal(a, b)
    assert bool(np.all(np.asarray(b.winner) < 6))


@pytest.mark.slow
def test_core_backend_parity_grid():
    """Full (p_miss x bits x N incl. padded) grid, scalar AND per-worker."""
    def prop(case):
        n, bits, p, mr = case["n"], case["bits"], case["p_miss"], case["mr"]
        if case["hetero"]:
            rng = np.random.default_rng(case["seed"] + 17)
            p = jnp.asarray(rng.uniform(0.0, max(p, 1e-6), n), jnp.float32)
        h = jnp.asarray(random_floats(case["seed"], (n, 64), specials=False))
        key = jax.random.PRNGKey(case["seed"])
        id_bits = ocs.host_id_bits(n)
        n_pad = case["n_pad"] or n
        mask = jnp.arange(n_pad) < n
        h_use = jnp.zeros((n_pad, 64), jnp.float32).at[:n].set(h)
        if case["hetero"]:
            p = jnp.zeros((n_pad,), jnp.float32).at[:n].set(p)
        a, b = _core_pair(h_use, mask, id_bits, key, p, bits=bits,
                          max_id_bits=ocs.host_id_bits(n_pad),
                          max_rounds=mr)
        _assert_results_equal(a, b, f"{case}: ")
    sweep(prop, list(grid(n=[3, 8], n_pad=[None, 12], bits=[8, 16],
                          p_miss=[0.0, 0.05, 0.4, 0.95], mr=[3],
                          hetero=[False, True], seed=[0])),
          label="core-grid")


def test_core_rounds_and_slots_hand_computed_via_pallas():
    """The p_miss~1 accounting identity holds through the kernel too:
    rounds == max_rounds, slots == max_rounds * total_bits * K,
    collisions == max_rounds * K, winner == worker 0."""
    n, k, bits, max_rounds = 5, 7, 10, 3
    h = jnp.asarray(random_floats(11, (n, k), specials=False))
    res = ocs.ocs_maxpool_noisy(h, jax.random.PRNGKey(0), bits=bits,
                                p_miss=1.0 - 1e-12, max_rounds=max_rounds,
                                backend="pallas")
    total_bits = bits + ocs.host_id_bits(n)
    assert int(res.rounds) == max_rounds
    assert int(res.contention_slots) == max_rounds * total_bits * k
    assert int(res.collisions) == max_rounds * k
    assert np.all(np.asarray(res.winner) == 0)


# ---------------------------------------------------------------------------
# model-level: maxpool_noisy(backend="pallas")
# ---------------------------------------------------------------------------

def test_maxpool_noisy_pallas_zero_miss_pins_to_quantized():
    """p_miss=0 reduction to maxpool_quantized(tie_break='first'), forward
    AND vjp, through the Pallas backend."""
    def prop(seed):
        h = jnp.asarray(random_floats(seed, (5, 7, 9), specials=False))
        key = jax.random.PRNGKey(seed)
        g = jnp.asarray(random_floats(seed + 100, (7, 9), specials=False))
        p0 = jnp.float32(0.0)
        for bits in (8, 16):
            out_n, vjp_n = jax.vjp(
                lambda x: fedocs.maxpool_noisy(x, key, p0, bits, 3,
                                               "pallas"), h)
            out_q, vjp_q = jax.vjp(
                lambda x: fedocs.maxpool_quantized(x, bits, "first"), h)
            assert np.array_equal(np.asarray(out_n), np.asarray(out_q))
            assert np.array_equal(np.asarray(vjp_n(g)[0]),
                                  np.asarray(vjp_q(g)[0]))
    sweep(prop, list(seeds(3)), "seed")


def test_maxpool_noisy_backends_agree_forward_and_vjp():
    """scan and pallas backends: same pooled value, same routed cotangent,
    at a miss rate that exercises re-contention."""
    h = jnp.asarray(random_floats(4, (6, 8, 16), specials=False))
    key = jax.random.PRNGKey(7)
    p = jnp.float32(0.3)
    g = jnp.asarray(random_floats(5, (8, 16), specials=False))
    outs, grads = [], []
    for backend in ("scan", "pallas"):
        out, vjp = jax.vjp(
            lambda x: fedocs.maxpool_noisy(x, key, p, 8, 3, backend), h)
        outs.append(np.asarray(out))
        grads.append(np.asarray(vjp(g)[0]))
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(grads[0], grads[1])


def test_maxpool_noisy_pallas_under_vmap_lanes():
    """The train-curve usage: one jitted step, lanes of traced (rng,
    p_miss), pallas backend — equal to the scan backend lane for lane."""
    h = jnp.asarray(random_floats(0, (4, 6, 8), specials=False))
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    ps = jnp.asarray([0.0, 0.1, 0.5], jnp.float32)
    traces = []

    def lane_fn(backend):
        @jax.jit
        def f(keys, ps):
            traces.append(backend)
            return jax.vmap(
                lambda k, p: fedocs.maxpool_noisy(h, k, p, 8, 3, backend)
            )(keys, ps)
        return f

    out_s = lane_fn("scan")(keys, ps)
    out_p = lane_fn("pallas")(keys, ps)
    assert np.array_equal(np.asarray(out_s), np.asarray(out_p))
    assert len(traces) == 2          # one compilation per backend


def test_core_rejects_unknown_backend():
    h = jnp.zeros((2, 4), jnp.float32)
    with pytest.raises(ValueError):
        ocs.ocs_maxpool_noisy(h, jax.random.PRNGKey(0), backend="triton")

"""jit'd public wrappers: sensing-draw packing + fused contention dispatch.

``noisy_contention`` is the entry point the protocol core
(``repro.core.ocs.ocs_maxpool_noisy_core(backend="pallas")``) calls: it
pre-draws the carrier-sensing stream with the *identical* per-(round,
sub-slot) Bernoulli calls the reference ``lax.scan`` makes — vmapped into
one batched threefry dispatch instead of ``max_rounds x n_slots`` sequential
ones — packs the draws into uint32 bit-planes, and hands the whole
tournament to the Pallas kernel.  Bit-for-bit parity with the scan backend
is a hard contract (tests/test_kernels_contention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ocs
from repro.kernels.ocs_contention import ocs_contention as K


def draw_heard_packed(rng: jax.Array, p_keep: jax.Array, n: int, k: int, *,
                      n_slots: int, max_rounds: int) -> jax.Array:
    """Pre-draw the sensing stream, packed along the sub-slot axis.

    Key derivation and draw order replicate the scan backend exactly:
    round r uses ``fold_in(rng, r)``, sub-slot d uses ``fold_in(key_r, d)``,
    and each sub-slot draws an (N, K) block via ``ocs.sensing_heard`` (the
    shared helper, so scalar and per-worker ``p_keep`` behave identically in
    both backends).  Returns (max_rounds, N, K) uint32 where bit
    ``n_slots - 1 - d`` of ``[r, n, k]`` is sub-slot d's draw.
    """
    r_keys = jax.vmap(lambda r: jax.random.fold_in(rng, r))(
        jnp.arange(max_rounds))
    rd_keys = jax.vmap(lambda kr: jax.vmap(
        lambda d: jax.random.fold_in(kr, d))(jnp.arange(n_slots)))(r_keys)
    heard = jax.vmap(jax.vmap(
        lambda key: ocs.sensing_heard(key, p_keep, n, k)))(rd_keys)
    plane = jnp.uint32(1) << (jnp.uint32(n_slots - 1)
                              - jnp.arange(n_slots, dtype=jnp.uint32))
    return jnp.sum(jnp.where(heard, plane[None, :, None, None],
                             jnp.uint32(0)), axis=1, dtype=jnp.uint32)


def contend(word: jax.Array, heard: jax.Array, mask: jax.Array,
            total_bits: jax.Array, *, n_slots: int, max_rounds: int,
            block_k: int = 1024, interpret: bool | None = None):
    """Kernel dispatch + reduction of the per-element accounting planes.

    Returns (winner (K,) int32, contending (max_rounds,) int32, collided
    (max_rounds,) int32) — the same contract as ``ref.contend``.
    ``interpret=None`` resolves via ``repro.kernels.interpret_default``.
    """
    winner, cont, coll = K.contend(
        word, heard, mask, total_bits, n_slots=n_slots,
        max_rounds=max_rounds, block_k=block_k, interpret=interpret)
    return (winner, jnp.sum(cont, axis=1, dtype=jnp.int32),
            jnp.sum(coll, axis=1, dtype=jnp.int32))


def noisy_contention(word: jax.Array, mask: jax.Array,
                     total_bits: jax.Array, rng: jax.Array,
                     p_keep: jax.Array, *, n_slots: int, max_rounds: int,
                     block_k: int = 1024, interpret: bool | None = None):
    """Draw the sensing stream and run the fused tournament.

    ``p_keep`` is ``ocs.sensing_keep_prob(p_miss, dtype)`` — () or (N, 1).
    """
    n, k = word.shape
    heard = draw_heard_packed(rng, p_keep, n, k, n_slots=n_slots,
                              max_rounds=max_rounds)
    return contend(word, heard, mask, total_bits, n_slots=n_slots,
                   max_rounds=max_rounds, block_k=block_k,
                   interpret=interpret)

"""Opportunistic Carrier Sensing (OCS) max-pooling protocol — paper §III, Alg. 1.

Discrete-event simulation of the MAC-layer distributed argmax.  The protocol
runs K sub-frames (one per feature element).  In sub-frame k, each worker n
derives a D-bit backoff code from its feature value ``h[n, k]`` (Eq. 7) and
contends bit-by-bit, MSB first:

  * sub-slot d: workers whose backoff bit is 0 transmit a *blocking signal*;
    workers whose backoff bit is 1 stay silent and *sense*.
  * a sensing worker that hears a blocking signal quits the contention
    (Alg. 1 line 4) — some still-alive worker provably holds a larger code;
  * if nobody transmitted in the slot, every survivor continues (no
    information was revealed; Alg. 1 line 7, "no ACK received").

After D sub-slots, the survivors are exactly the workers holding the maximal
D-bit code.  The paper's ACK mechanism resolves ties; we realize it as a
deterministic extension: ``ceil(log2 N)`` extra ID sub-slots in which each
survivor contends with the bitwise complement of its unique worker index, so
the *lowest-indexed* tied worker wins (this is the fusion center ACK-ing a
single decodable preamble).  The winner then transmits its payload
(Alg. 1 line 9).

Two layers:

  * ``ocs_maxpool_core`` / ``ocs_maxpool_noisy_core`` — batched cores.  They
    take a padded worker axis plus a boolean ``mask`` of real workers and a
    *traced* ``id_bits``, so one compiled computation can evaluate many
    ``(N, p_miss)`` scenarios via ``vmap`` (see ``repro.sim.sweep``).  The
    bit-slot scan runs a static ``bits + max_id_bits`` sub-slots; slots past
    the scenario's ``bits + id_bits`` are inert, so the channel accounting is
    bit-for-bit identical to an unpadded run.
  * ``ocs_maxpool`` / ``ocs_maxpool_noisy`` — the single-round convenience
    wrappers (all workers real, exact scan length), used by the tests and
    the protocol-equivalence oracles.

The simulator is fully vectorized (a `lax.scan` over bit-slots) and jittable;
it returns both the selection result and the channel accounting used by
``benchmarks/bench_comm.py`` to reproduce the paper's O(K)-vs-O(N·K) claim.

The TPU system does not use this MAC (DESIGN.md §2 — ICI is a switched
fabric); the simulator exists to validate the protocol the paper actually
proposes and to generate the wireless-side communication-load tables.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import quantize as qz


@dataclasses.dataclass(frozen=True)
class OCSResult:
    """Outcome of one max-pooling round over the shared channel."""

    winner: jax.Array            # (K,) int32 — worker index that transmits element k
    value: jax.Array             # (K,) float — payload transmitted (winner's h)
    pooled_code: jax.Array       # (K,) uint  — max D-bit code (what contention decides)
    ties: jax.Array              # (K,) int32 — number of workers tied at the max code
    contention_slots: jax.Array  # ()  int32  — total contention sub-slots consumed
    blocking_tx: jax.Array       # ()  int32  — total blocking-signal transmissions
    payload_tx: jax.Array        # ()  int32  — total payload transmissions (== K)
    # baselines for the same round (paper §IV comparison):
    concat_payload_tx: jax.Array  # () int32 — N*K payloads (concat / mean-pool)


@dataclasses.dataclass(frozen=True)
class NoisyOCSResult:
    """Outcome under imperfect sensing (the paper assumes error-free §IV)."""

    winner: jax.Array            # (K,) int32 — final payload transmitter
    correct: jax.Array           # (K,) bool  — winner holds the true max code
    collisions: jax.Array        # ()  int32  — sub-frames needing re-contention
    rounds: jax.Array            # ()  int32  — rounds until every sub-frame
    #   resolved (== max_rounds when lowest-index capture was needed)
    contention_slots: jax.Array  # ()  int32  — re-contention counts only the
    #   sub-frames still unresolved at the start of each round


@dataclasses.dataclass(frozen=True)
class MultichannelOCSResult:
    """OFDMA variant outcome: untouched protocol accounting + channel latency.

    ``result.contention_slots`` keeps the *total* contention sub-slots (the
    transmission count consumers read); the wall-clock benefit of striping
    over orthogonal channels lives in ``latency_slots`` only, mirroring
    ``repro.sim.sweep.SweepResult.*_latency_slots``.
    """

    result: OCSResult
    latency_slots: jax.Array     # () int32 — ceil(contention_slots / n_channels)


# Registered as pytrees so the batched cores can return them through
# jit/vmap and the sweep engine can stack them along scenario/round axes.
for _cls in (OCSResult, NoisyOCSResult, MultichannelOCSResult):
    jax.tree_util.register_dataclass(
        _cls,
        data_fields=[f.name for f in dataclasses.fields(_cls)],
        meta_fields=[],
    )


def host_id_bits(n_workers: int) -> int:
    """ID sub-slots needed to tie-break N workers: ceil(log2(max(N, 2)))."""
    return max(1, math.ceil(math.log2(max(n_workers, 2))))


def _id_codes(n_workers: int, id_bits: jax.Array) -> jax.Array:
    """Per-worker tie-break codes: complement of index => lowest index wins max.

    ``id_bits`` may be traced; codes for indices >= 2**id_bits wrap around in
    uint32 — those rows must be masked out by the caller (padded workers).
    """
    idx = jnp.arange(n_workers, dtype=jnp.uint32)
    top = (jnp.uint32(1) << jnp.asarray(id_bits).astype(jnp.uint32)) - jnp.uint32(1)
    return top - idx


def sensing_keep_prob(p_miss: jax.Array, dtype) -> jax.Array:
    """Per-sub-slot hear probability, broadcastable over an (N, K) slot.

    ``p_miss`` is either a scalar (every worker senses equally well) or a
    per-worker ``(N,)`` array (heterogeneous near/far users: a far worker
    overhears blocking signals with lower probability).  Returns ``1 - p``
    shaped ``()`` or ``(N, 1)`` so ``bernoulli(key, p_keep, (N, K))`` draws
    the worker axis down the leading dimension.  With every entry equal the
    vector path is bit-for-bit the scalar path (the uniform draw does not
    depend on the threshold; property-tested).
    """
    dt = dtype if jnp.issubdtype(jnp.dtype(dtype), jnp.floating) else jnp.float32
    p = jnp.asarray(p_miss, dt)
    if p.ndim == 0:
        return 1.0 - p
    if p.ndim == 1:
        return 1.0 - p[:, None]
    raise ValueError(f"p_miss must be scalar or (N,), got shape {p.shape}")


def sensing_heard(key: jax.Array, p_keep: jax.Array, n: int, k: int) -> jax.Array:
    """One sub-slot of carrier-sensing draws: heard[n, k] ~ Bern(p_keep[n]).

    The single place the sensing randomness is drawn — the ``lax.scan``
    protocol core consumes it slot by slot and the fused Pallas contention
    kernel (``repro.kernels.ocs_contention``) pre-draws the identical stream
    by vmapping this helper over the (round, sub-slot) key grid, which keeps
    the two backends bit-for-bit interchangeable.
    """
    return jax.random.bernoulli(key, p_keep, (n, k))


def ocs_maxpool_core(h: jax.Array, mask: jax.Array, id_bits: jax.Array, *,
                     bits: int, max_id_bits: int) -> OCSResult:
    """Batched Algorithm 1 core over a padded worker axis.

    Args:
      h:           (N_max, K) worker feature matrix; padded rows are ignored.
      mask:        (N_max,) bool — True for real workers (>=1 must be real).
      id_bits:     () int32 — tie-break sub-slots for the *real* worker count
                   (``host_id_bits(n)``); may be a traced value so scenarios
                   with different N share one compilation.
      bits:        D, the backoff quantization depth (static).
      max_id_bits: static scan-length bound; must satisfy
                   ``max_id_bits >= id_bits`` for every batched scenario.

    Returns:
      OCSResult with accounting identical, bit for bit, to an unpadded
      ``ocs_maxpool`` run at the real worker count (property-tested in
      ``tests/test_sweep.py``): sub-slots past ``bits + id_bits`` are gated
      off, so neither ``contention_slots`` nor ``blocking_tx`` see them.
    """
    if bits + max_id_bits > 32:
        raise ValueError(
            f"contention word overflows uint32: bits={bits} + "
            f"max_id_bits={max_id_bits} > 32")
    n_max, k_elems = h.shape
    qcodes = qz.quantize(h, bits)                              # (N_max, K)
    codes = qcodes.astype(jnp.uint32)
    id_bits = jnp.asarray(id_bits, jnp.int32)
    ids = _id_codes(n_max, id_bits)                            # (N_max,)
    # Full contention word: [ value code | id code ] — MSB-first tournament
    # over this word is (a) Alg. 1 for the top `bits` slots, (b) the ACK
    # tie-break for the bottom `id_bits` slots.
    word = (codes << id_bits.astype(jnp.uint32)) | ids[:, None]  # (N_max, K)
    total_bits = bits + id_bits                                # () int32

    def slot(carry, d):
        alive, slots, blocks = carry
        active = d < total_bits
        shift = jnp.maximum(total_bits - 1 - d, 0).astype(jnp.uint32)
        bit = (word >> shift) & jnp.uint32(1)                  # (N_max, K)
        tx = alive & (bit == 1) & active                       # blocking transmitters
        any_tx = jnp.any(tx, axis=0, keepdims=True)            # (1, K)
        # sensing workers (bit==0) quit iff someone transmitted (Alg.1 l.3-4);
        # otherwise everyone continues (Alg.1 l.6-7).  Inactive (padding)
        # slots transmit nothing, so they are no-ops.
        alive = alive & (tx | ~any_tx)
        slots = slots + jnp.where(active, k_elems, 0).astype(jnp.int32)
        blocks = blocks + jnp.sum(tx, dtype=jnp.int32)
        return (alive, slots, blocks), None

    alive0 = jnp.broadcast_to(mask[:, None], (n_max, k_elems))
    (alive, slots, blocks), _ = jax.lax.scan(
        slot,
        (alive0, jnp.int32(0), jnp.int32(0)),
        jnp.arange(bits + max_id_bits),
    )

    # After value+id slots exactly one real worker survives per sub-frame.
    winner = jnp.argmax(alive, axis=0).astype(jnp.int32)       # (K,)
    at_max = (codes == jnp.max(jnp.where(mask[:, None], codes, 0),
                               axis=0)[None, :]) & mask[:, None]
    pooled_code = jnp.max(jnp.where(mask[:, None], codes, 0), axis=0)
    ties = jnp.sum(at_max, axis=0).astype(jnp.int32)
    value = jnp.take_along_axis(h, winner[None, :], axis=0)[0]
    n_workers = jnp.sum(mask, dtype=jnp.int32)

    return OCSResult(
        winner=winner,
        value=value,
        pooled_code=pooled_code.astype(qcodes.dtype),
        ties=ties,
        contention_slots=slots,
        blocking_tx=blocks,
        payload_tx=jnp.int32(k_elems),
        concat_payload_tx=n_workers * k_elems,
    )


def ocs_maxpool(h: jax.Array, bits: int = 16) -> OCSResult:
    """Run Algorithm 1 for all K sub-frames of one aggregation round.

    Args:
      h:    (N, K) worker feature matrix (float32/bf16/f16).
      bits: D, the backoff quantization depth (paper Eq. 7).

    Returns:
      OCSResult. ``winner``/``pooled_code`` are exactly
      ``argmax/max(quantize(h), axis=0)`` with lowest-index tie-break — this
      equivalence is property-tested in ``tests/test_ocs.py``.
    """
    if h.ndim != 2:
        raise ValueError(f"h must be (N, K), got {h.shape}")
    n_workers = h.shape[0]
    id_bits = host_id_bits(n_workers)
    return ocs_maxpool_core(
        h, jnp.ones((n_workers,), dtype=bool), id_bits,
        bits=bits, max_id_bits=id_bits)


def ocs_maxpool_multichannel(h: jax.Array, bits: int = 16,
                             n_channels: int = 4) -> MultichannelOCSResult:
    """Multi-channel (OFDMA) variant — paper §III ref [16].

    K sub-frames are striped over ``n_channels`` orthogonal channels running
    the same contention in parallel; selection results and total slot counts
    are identical, wall time divides by ``n_channels``.  The returned
    ``result`` is exactly the single-channel :func:`ocs_maxpool` outcome
    (``contention_slots`` stays the total transmission-slot count);
    ``latency_slots`` carries the striped wall-clock figure.
    """
    res = ocs_maxpool(h, bits)
    # contention latency improves; transmission counts are unchanged.
    return MultichannelOCSResult(
        result=res,
        latency_slots=(res.contention_slots + n_channels - 1) // n_channels,
    )


def reference_maxpool(h: jax.Array, bits: int):
    """Pure-jnp oracle for the protocol outcome (used by tests)."""
    codes = qz.quantize(h, bits)
    pooled_code = jnp.max(codes, axis=0)
    winner = jnp.argmax(codes == pooled_code[None, :], axis=0).astype(jnp.int32)
    value = jnp.take_along_axis(h, winner[None, :], axis=0)[0]
    return winner, value, pooled_code


# ---------------------------------------------------------------------------
# beyond-paper: imperfect carrier sensing
# ---------------------------------------------------------------------------

NOISY_BACKENDS = ("scan", "pallas")


def ocs_maxpool_noisy_core(h: jax.Array, mask: jax.Array, id_bits: jax.Array,
                           rng: jax.Array, p_miss: jax.Array, *,
                           bits: int, max_id_bits: int,
                           max_rounds: int = 3,
                           backend: str = "scan") -> NoisyOCSResult:
    """Batched imperfect-sensing core (padded N, traced ``id_bits``/``p_miss``).

    Same contract as :func:`ocs_maxpool_core`; additionally ``p_miss`` may be
    a traced scalar — or a per-worker ``(N_max,)`` array for heterogeneous
    near/far users — so a whole miss-probability axis of a scenario grid
    shares one compilation.  With ``max_id_bits == id_bits`` the random-bit
    consumption matches the historical unbatched implementation exactly.

    ``backend`` selects the contention engine:

      * ``"scan"``  — the reference ``lax.scan`` over (max_rounds x sub-slot)
        steps, one Bernoulli draw + alive update per sub-slot;
      * ``"pallas"`` — the fused ``repro.kernels.ocs_contention`` kernel: the
        sensing stream is pre-drawn in one batched call and packed into
        uint32 bit-planes, and the whole tournament runs in a single VMEM
        pass (interpret-mode on CPU hosts).  Bit-for-bit identical to
        ``"scan"`` in every ``NoisyOCSResult`` field (property-tested in
        ``tests/test_kernels_contention.py``).
    """
    if bits + max_id_bits > 32:
        raise ValueError(
            f"contention word overflows uint32: bits={bits} + "
            f"max_id_bits={max_id_bits} > 32")
    if backend not in NOISY_BACKENDS:
        raise ValueError(
            f"unknown noisy-OCS backend {backend!r}; valid: {NOISY_BACKENDS}")
    n_max, k_elems = h.shape
    codes = qz.quantize(h, bits).astype(jnp.uint32)
    id_bits = jnp.asarray(id_bits, jnp.int32)
    ids = _id_codes(n_max, id_bits)
    word = (codes << id_bits.astype(jnp.uint32)) | ids[:, None]
    total_bits = bits + id_bits
    n_slots = bits + max_id_bits
    p_keep = sensing_keep_prob(p_miss, h.dtype)

    if backend == "pallas":
        # imported lazily: the kernels layer is optional and core must not
        # pull Pallas in for scan-only users.
        from repro.kernels.ocs_contention import ops as contention_ops

        winner, contending, collided = contention_ops.noisy_contention(
            word, mask, total_bits, rng, p_keep,
            n_slots=n_slots, max_rounds=max_rounds)
        # pin the accumulators: jnp.sum promotes int/bool to the platform
        # int, which becomes int64 under JAX_ENABLE_X64
        slots = (total_bits.astype(jnp.int32)
                 * jnp.sum(contending, dtype=jnp.int32))
        rounds = jnp.sum(contending > 0, dtype=jnp.int32)
        collisions = jnp.sum(collided, dtype=jnp.int32)
    else:
        def contention_round(alive, key):
            def slot(alive, d):
                active = d < total_bits
                shift = jnp.maximum(total_bits - 1 - d, 0).astype(jnp.uint32)
                bit = (word >> shift) & jnp.uint32(1)
                tx = alive & (bit == 1) & active
                any_tx = jnp.any(tx, axis=0, keepdims=True)
                with jax.named_scope("ocs.sense"):
                    heard = sensing_heard(
                        jax.random.fold_in(key, d), p_keep, n_max, k_elems)
                # a sensing worker quits only if someone transmitted AND it
                # heard
                alive = alive & (tx | ~(any_tx & heard))
                return alive, None

            alive, _ = jax.lax.scan(slot, alive, jnp.arange(n_slots))
            return alive

        def round_body(carry, r):
            alive, slots, rounds, done = carry
            key = jax.random.fold_in(rng, r)
            # only sub-frames still unresolved at round start re-contend:
            # they alone consume channel slots (bits + id_bits sub-slots
            # each); a resolved sub-frame's lone survivor keeps its claim
            # untouched.
            contending = jnp.sum(~done, dtype=jnp.int32)      # () sub-frames
            survivors = contention_round(alive, key)
            n_surv = jnp.sum(survivors, axis=0)               # (K,)
            collided = n_surv > 1
            # collided sub-frames re-contend among survivors; resolved keep
            # winner
            new_done = done | ~collided
            slots = slots + total_bits.astype(jnp.int32) * contending
            rounds = rounds + (contending > 0).astype(jnp.int32)
            return (survivors, slots, rounds, new_done), jnp.sum(
                collided, dtype=jnp.int32)

        alive0 = jnp.broadcast_to(mask[:, None], (n_max, k_elems))
        done0 = jnp.zeros((k_elems,), dtype=bool)
        (alive, slots, rounds, done), coll_rounds = jax.lax.scan(
            round_body, (alive0, jnp.int32(0), jnp.int32(0), done0),
            jnp.arange(max_rounds))
        winner = jnp.argmax(alive, axis=0).astype(jnp.int32)  # lowest-idx cap
        collisions = jnp.sum(coll_rounds, dtype=jnp.int32)

    true_code = jnp.max(jnp.where(mask[:, None], codes, 0), axis=0)
    correct = jnp.take_along_axis(codes, winner[None, :], axis=0)[0] \
        == true_code
    return NoisyOCSResult(
        winner=winner,
        correct=correct,
        collisions=collisions,
        rounds=rounds,
        contention_slots=slots,
    )


def ocs_maxpool_noisy(h: jax.Array, rng: jax.Array, bits: int = 16,
                      p_miss: float = 0.0, max_rounds: int = 3,
                      backend: str = "scan") -> NoisyOCSResult:
    """Algorithm 1 with miss-detection: a sensing worker overhears a blocking
    signal with probability ``1 - p_miss`` per sub-slot.  Missed detections
    create false survivors; when several survivors transmit payloads the
    fusion center detects the collision (no clean ACK) and the survivors
    re-contend (up to ``max_rounds``, then lowest-index capture).

    With ``p_miss=0`` this reduces exactly to :func:`ocs_maxpool`
    (property-tested).  ``p_miss`` is a scalar or a per-worker ``(N,)``
    array (near/far users).  The fusion result degrades gracefully: an
    incorrect winner still transmits *its own true value*, so the pooled
    feature is a lower bound of the true max — the learner sees a noisy
    max-pool, never a corrupted value.
    """
    if h.ndim != 2:
        raise ValueError(f"h must be (N, K), got {h.shape}")
    n_workers = h.shape[0]
    id_bits = host_id_bits(n_workers)
    return ocs_maxpool_noisy_core(
        h, jnp.ones((n_workers,), dtype=bool), id_bits, rng, p_miss,
        bits=bits, max_id_bits=id_bits, max_rounds=max_rounds,
        backend=backend)

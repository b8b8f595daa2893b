"""Fused noisy-contention Pallas kernel (Alg. 1 + miss detection, on-chip).

The noisy-OCS winner selection used by ``fedocs.maxpool_noisy`` is the curve
runner's dominant step-time cost: a ``lax.scan`` over
``max_rounds x (bits + id_bits)`` sub-slots, each step re-deriving a threefry
sub-key, drawing an (N, K) Bernoulli block, and materializing the alive mask
through HBM.  This kernel runs the entire tournament — every round, every
sub-slot — as one VMEM pass per (N, BK) tile:

  * the *sensing stream* is pre-drawn outside (``ops.draw_heard_packed``
    vmaps the exact per-sub-slot Bernoulli calls the scan makes, so the two
    backends stay bit-for-bit interchangeable) and packed along the sub-slot
    axis into one uint32 **bit-plane word per (round, worker, element)** —
    8-32x less HBM traffic than per-slot boolean blocks, and the in-kernel
    sub-slot loop becomes plain shift/mask arithmetic on registers;
  * the contention itself is a bit-plane reduction over the *leading* worker
    axis: for each sub-slot the transmit set is a shift of the contention
    word, the blocking condition a max over workers, and the alive mask
    never leaves VMEM;
  * the rounds/slots/collision accounting is emitted per element column
    (unresolved at round start, collided per round: ``(max_rounds, K)``
    0/1 planes) and the wrapper sums them over K — integer sums, so the
    accounting is exactly the scan's.

Both loops are unrolled at trace time (``max_rounds <= 4`` and
``n_slots <= 32`` by the 32-bit contention-word guard), which keeps every
memory access statically indexed.  ``total_bits`` stays a *traced* scalar
(a (1, 1) int32 operand in SMEM) so the sweep engine's padded scenarios
(``max_id_bits > id_bits``) share one compilation: sub-slots past
``total_bits`` compute but are gated inactive, exactly like the scan.

Mosaic layout: the alive/transmit state is int32 0/1 (boolean vectors do
not reduce over sublanes), the winner is a masked ``min`` over a worker
iota instead of an ``argmax``, and every output is a lane-dense row block.

Tiling: 1-D grid over K / BK element columns, BK a multiple of 128 lanes
(K is zero-padded up to a whole number of tiles and the padding sliced off
again); the worker axis (N <= 64 for every registered scenario) always fits
the tile, so the reduction never crosses tiles.  Validated bit-for-bit
against ``ref.py`` and the scan core in ``tests/kernel_parity.py`` /
``tests/test_kernels_contention.py``; ``tests/test_tpu_compile.py`` compiles
it for a v5e chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_default

LANES = 128


def _contention_kernel(tb_ref, word_ref, heard_ref, mask_ref,
                       winner_ref, cont_ref, coll_ref, *,
                       n_slots: int, max_rounds: int):
    word = word_ref[...]                              # (N, BK) uint32
    n, bk = word.shape
    tb = tb_ref[0, 0]                                 # () int32, traced
    alive = (jnp.broadcast_to(mask_ref[...], word.shape) != 0).astype(
        jnp.int32)                                    # 0/1 per (worker, elem)
    done = jnp.zeros((1, bk), jnp.int32)              # resolved sub-frames
    one = jnp.uint32(1)
    for r in range(max_rounds):
        heard_r = heard_ref[r]                        # (N, BK) packed planes
        # unresolved sub-frames at round start: these alone bill channel
        # slots (the wrapper multiplies the sum by total_bits)
        cont_ref[r:r + 1, :] = 1 - done
        for d in range(n_slots):
            # inactive (padded) sub-slots transmit nothing: AND with 0
            act = (jnp.int32(d) < tb).astype(jnp.uint32)
            shift = jnp.maximum(tb - 1 - d, 0).astype(jnp.uint32)
            bit = ((word >> shift) & act).astype(jnp.int32)
            heard = ((heard_r >> jnp.uint32(n_slots - 1 - d)) & one).astype(
                jnp.int32)
            tx = alive * bit
            any_tx = jnp.max(tx, axis=0, keepdims=True)
            # a sensing worker quits only if someone transmitted AND it heard
            alive = alive * jnp.maximum(tx, 1 - any_tx * heard)
        n_surv = jnp.sum(alive, axis=0, keepdims=True)
        collided = (n_surv > 1).astype(jnp.int32)
        coll_ref[r:r + 1, :] = collided
        done = jnp.maximum(done, 1 - collided)
    # lowest-index capture: first alive worker per element column (worker 0
    # when none is alive, as argmax over an all-False column gives)
    workers = jax.lax.broadcasted_iota(jnp.int32, word.shape, 0)
    first = jnp.min(jnp.where(alive > 0, workers, n), axis=0, keepdims=True)
    winner_ref[...] = jnp.where(first == n, 0, first)


@functools.partial(jax.jit, static_argnames=("n_slots", "max_rounds",
                                             "block_k", "interpret"))
def _contend_jit(word, heard, mask, total_bits, *, n_slots, max_rounds,
                 block_k, interpret):
    n, k = word.shape
    bk = min(block_k, pl.cdiv(k, LANES) * LANES)
    k_pad = pl.cdiv(k, bk) * bk
    heard = heard.astype(jnp.uint32)
    if k_pad != k:
        word = jnp.pad(word, ((0, 0), (0, k_pad - k)))
        heard = jnp.pad(heard, ((0, 0), (0, 0), (0, k_pad - k)))
    winner, cont, coll = pl.pallas_call(
        functools.partial(_contention_kernel, n_slots=n_slots,
                          max_rounds=max_rounds),
        grid=(k_pad // bk,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, bk), lambda j: (0, j)),
            pl.BlockSpec((max_rounds, n, bk), lambda j: (0, 0, j)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk), lambda j: (0, j)),
            pl.BlockSpec((max_rounds, bk), lambda j: (0, j)),
            pl.BlockSpec((max_rounds, bk), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((max_rounds, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((max_rounds, k_pad), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(total_bits, jnp.int32).reshape(1, 1), word, heard,
      mask.astype(jnp.int32).reshape(n, 1))
    return winner[0, :k], cont[:, :k], coll[:, :k]


def contend(word: jax.Array, heard: jax.Array, mask: jax.Array,
            total_bits: jax.Array, *, n_slots: int, max_rounds: int,
            block_k: int = 1024, interpret: bool | None = None):
    """Run the full noisy tournament over packed bit-planes.

    Args:
      word:       (N, K) uint32 — [value code | id code] contention words.
      heard:      (max_rounds, N, K) uint32 — sensing draws packed along the
                  sub-slot axis; bit ``n_slots - 1 - d`` of ``heard[r, n, k]``
                  is sub-slot d's draw (see ``ops.draw_heard_packed``).
      mask:       (N,) bool — real (non-padded) workers.
      total_bits: () int32 — live sub-slots ``bits + id_bits``; may be
                  traced.  Sub-slots past it are inert (padded scan bound).
      n_slots:    static sub-slot count per round (``bits + max_id_bits``).
      max_rounds: static re-contention bound.
      block_k:    element columns per tile, a multiple of 128 (smaller K
                  runs as one tile of K rounded up to 128).
      interpret:  ``None`` resolves via ``repro.kernels.interpret_default``
                  (compiled on real TPU, interpreted elsewhere).

    Returns:
      winner:     (K,) int32 — surviving worker per element (lowest-index
                  capture among survivors).
      contending: (max_rounds, K) int32 — 1 where the element was still
                  unresolved at the round's start.
      collided:   (max_rounds, K) int32 — 1 where the element collided in
                  the round.
    """
    if not (1 <= n_slots <= 32):
        raise ValueError(f"n_slots must be in [1, 32], got {n_slots}")
    if block_k < LANES or block_k % LANES:
        raise ValueError(
            f"block_k must be a positive multiple of {LANES}, got {block_k}")
    if interpret is None:
        interpret = interpret_default()
    return _contend_jit(word, heard, mask, total_bits, n_slots=n_slots,
                        max_rounds=max_rounds, block_k=block_k,
                        interpret=interpret)

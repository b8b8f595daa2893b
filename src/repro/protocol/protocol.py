"""First-class wireless-aggregation protocol objects.

The paper's contribution is a *protocol* — D-bit quantized embeddings
max-pooled over a shared channel via opportunistic carrier sensing (§II-B,
Eq. 4-7) — and :class:`Protocol` makes it a value instead of a
``mode="max_noisy"`` string plus loose kwargs.  One frozen, pytree-registered
object carries every protocol-side knob and answers every question its
consumers used to scatter across ``fedocs.aggregate``, ``ChannelNoise``,
``VerticalConfig`` and the ``channel.py`` load helpers:

  * ``protocol.aggregate(h, rng) -> (pooled, ProtocolAccounting)`` — the
    aggregation law itself, with the winner-routed ``custom_vjp`` backward
    (paper Eq. 5-6) unchanged and bit-for-bit identical to the historical
    string-mode paths for every kind on both contention backends;
  * ``protocol.comm_load(n_workers, k)`` — the analytic uplink/latency
    accounting (paper §I / §IV), with ``payload_bits`` resolved from ONE
    source of truth (the protocol's own quantization depth, unless
    explicitly overridden);
  * ``protocol.output_dim(n_workers, k)`` — the fused feature width the
    head sees.

Pytree layout: ``p_miss`` (traced scalar or per-worker ``(N,)`` miss
probability) and ``online`` (optional ``(N,)`` worker-up mask, default
``None`` = everyone contends) are the only leaves, so a single compiled
computation (or a ``vmap`` lane axis) serves a whole miss-probability or
fault grid; every other field is static metadata
(``kind``, ``bits``, ``backend``, ``max_rounds``, ``tie_break``,
``n_channels``, ``payload_bits``) baked into the compiled program.  The
quantization depth ``bits`` stays static because it selects the code dtype
(uint8/uint16) and the contention scan length; depth *scheduling* across
training is instead expressed with :class:`repro.protocol.BitsSchedule`,
which switches between per-``bits`` compiled branches on device.

Construct protocols with the named constructors::

    Protocol.ocs(bits=8, p_miss=0.05)      # noisy-OCS channel in the loop
    Protocol.ideal_max(bits=16)            # error-free quantized max-pool
    Protocol.max() / .mean() / .concat() / .sum()   # paper baselines
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import channel, fedocs, ocs

KINDS = ("sum", "max", "ideal_max", "ocs", "mean", "concat")

# string-mode names (fedocs.VALID_MODES) -> Protocol constructor arguments
_MODE_TO_KIND = {
    "sum": "sum",
    "max": "max",
    "max_q16": "ideal_max",
    "max_q8": "ideal_max",
    "max_noisy": "ocs",
    "mean": "mean",
    "concat": "concat",
}


@dataclasses.dataclass(frozen=True)
class ProtocolAccounting:
    """Measured channel accounting of one ``Protocol.aggregate`` call.

    Non-trivial only for ``kind="ocs"`` (the simulated noisy contention);
    ideal collectives report zeros — they consume no simulated channel.
    ``collisions`` counts collided (sub-frame, round) events — a sub-frame
    is billed once per round it stays collided, so the total lies in
    ``[0, K * max_rounds]`` — ``rounds`` the contention rounds until every
    sub-frame resolved, and ``contention_slots`` the sub-slots billed to
    unresolved sub-frames — exactly the ``NoisyOCSResult`` counters of the
    contention core.
    ``correct_frac`` is the fraction of elements whose winner held the true
    max code (the accuracy telemetry :class:`repro.protocol.BitsSchedule`
    policies may consume).
    """

    rounds: jax.Array            # () int32
    collisions: jax.Array        # () int32
    contention_slots: jax.Array  # () int32
    correct_frac: jax.Array      # () float32

    @staticmethod
    def zeros() -> "ProtocolAccounting":
        return ProtocolAccounting(
            rounds=jnp.int32(0), collisions=jnp.int32(0),
            contention_slots=jnp.int32(0), correct_frac=jnp.float32(1.0))


jax.tree_util.register_dataclass(
    ProtocolAccounting,
    data_fields=["rounds", "collisions", "contention_slots", "correct_frac"],
    meta_fields=[])


# ---------------------------------------------------------------------------
# the noisy-OCS pooling law with accounting: custom_vjp, Eq. 5-6 backward
# ---------------------------------------------------------------------------

def _acct_from(res: ocs.NoisyOCSResult) -> ProtocolAccounting:
    return ProtocolAccounting(
        rounds=res.rounds, collisions=res.collisions,
        contention_slots=res.contention_slots,
        correct_frac=jnp.mean(res.correct.astype(jnp.float32)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ocs_pool(h, rng, p_miss, online, bits, max_rounds, backend):
    """``fedocs.maxpool_noisy`` + the contention core's channel accounting.

    Shares ``fedocs._maxpool_noisy_impl`` with :func:`fedocs.maxpool_noisy`,
    so the pooled value, the winner-routed backward AND the accounting are
    bit-for-bit the historical path (the accounting was always computed by
    the core; it was just discarded before reaching the caller).  ``online``
    is the all-``True`` mask unless the protocol carries a dropout state
    (``repro.faults``): dark workers leave the contention entirely.
    """
    pooled, _, res = fedocs._maxpool_noisy_impl(h, rng, p_miss, bits,
                                                max_rounds, backend,
                                                online=online)
    return pooled, _acct_from(res)


def _ocs_pool_fwd(h, rng, p_miss, online, bits, max_rounds, backend):
    pooled, mask, res = fedocs._maxpool_noisy_impl(h, rng, p_miss, bits,
                                                   max_rounds, backend,
                                                   online=online)
    return (pooled, _acct_from(res)), (mask, rng, p_miss, online)


def _ocs_pool_bwd(bits, max_rounds, backend, residuals, g):
    mask, rng, p_miss, online = residuals
    g_pooled, _g_acct = g        # accounting is non-differentiable telemetry
    d_rng = np.zeros(np.shape(rng), jax.dtypes.float0)
    d_online = np.zeros(np.shape(online), jax.dtypes.float0)
    return (g_pooled[None] * mask, d_rng, jnp.zeros_like(p_miss), d_online)


_ocs_pool.defvjp(_ocs_pool_fwd, _ocs_pool_bwd)


# ---------------------------------------------------------------------------
# the Protocol object
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Protocol:
    """One wireless aggregation protocol as a frozen pytree value.

    Do not call the constructor directly — use the named constructors
    (:meth:`ocs`, :meth:`ideal_max`, :meth:`max`, :meth:`mean`,
    :meth:`concat`, :meth:`sum`, or :meth:`from_mode` for legacy
    string-mode names).  ``p_miss`` and ``online`` are the only pytree
    leaves; all other fields are static metadata.
    """

    kind: str                       # one of KINDS
    bits: Optional[int] = None      # D, backoff/payload depth (static)
    tie_break: str = "all"          # gradient routing at code ties
    max_rounds: int = 3             # ocs: re-contention bound
    backend: str = "scan"           # ocs: "scan" | "pallas" contention engine
    n_channels: int = 1             # OFDMA channels (comm_load latency)
    payload_bits: Optional[int] = None   # comm_load override; None derives
    #   from the protocol itself (D-bit code payload for ocs/ideal_max,
    #   full 32-bit float payload otherwise)
    p_miss: Optional[jax.Array] = None   # traced leaf: () or (N,) miss prob;
    #   None = unbound (supply per call via with_p_miss)
    online: Optional[jax.Array] = None   # traced leaf: (N,) bool worker-up
    #   mask; None = all workers contend (bit-for-bit the all-True mask)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown protocol kind {self.kind!r}; valid: {KINDS}")
        if self.kind in ("ideal_max", "ocs", "max"):
            if self.bits is None or not (1 <= self.bits <= 32):
                raise ValueError(
                    f"{self.kind} protocol needs bits in [1, 32], "
                    f"got {self.bits}")
        if self.tie_break not in ("all", "first"):
            raise ValueError(f"unknown tie_break {self.tie_break!r}")
        if self.kind == "ocs":
            if self.backend not in ocs.NOISY_BACKENDS:
                raise ValueError(
                    f"unknown ocs backend {self.backend!r}; "
                    f"valid: {ocs.NOISY_BACKENDS}")
            if self.max_rounds < 1:
                raise ValueError("max_rounds must be >= 1")
        if self.n_channels < 1:
            raise ValueError("n_channels must be >= 1")

    # -- constructors -------------------------------------------------------

    @classmethod
    def sum(cls, *, n_channels: int = 1) -> "Protocol":
        """All-reduce(add) fusion (Megatron-style TP reference)."""
        return cls(kind="sum", n_channels=n_channels)

    @classmethod
    def max(cls, *, bits: int = 16, tie_break: str = "all",
            n_channels: int = 1) -> "Protocol":
        """Ideal float max-pool (paper Eq. 4): the D ``bits`` drive the
        contention accounting only; the winner transmits its full float."""
        return cls(kind="max", bits=bits, tie_break=tie_break,
                   n_channels=n_channels, payload_bits=32)

    @classmethod
    def ideal_max(cls, bits: int, *, tie_break: str = "all",
                  n_channels: int = 1) -> "Protocol":
        """Error-free quantized max-pool on D-bit monotone codes (Eq. 7):
        the winner's uplink payload is the D-bit code itself."""
        return cls(kind="ideal_max", bits=bits, tie_break=tie_break,
                   n_channels=n_channels)

    @classmethod
    def ocs(cls, bits: int = 16, p_miss=None, *, max_rounds: int = 3,
            backend: str = "scan", n_channels: int = 1,
            payload_bits: Optional[int] = None) -> "Protocol":
        """The paper's OCS channel with imperfect carrier sensing in the
        loop: quantized D-bit contention, per-sub-slot miss detection,
        lowest-index capture after ``max_rounds``.  ``p_miss`` is a traced
        scalar or per-worker ``(N,)`` array (it may stay ``None`` and be
        bound per call via :meth:`with_p_miss`)."""
        return cls(kind="ocs", bits=bits, tie_break="first",
                   max_rounds=max_rounds, backend=backend,
                   n_channels=n_channels, payload_bits=payload_bits,
                   p_miss=p_miss)

    @classmethod
    def mean(cls, *, n_channels: int = 1) -> "Protocol":
        """Mean-pool baseline (paper "Avg. Workers Embed")."""
        return cls(kind="mean", n_channels=n_channels)

    @classmethod
    def concat(cls, *, n_channels: int = 1) -> "Protocol":
        """Concat baseline (paper "Concat Workers Embed", O(N*K) uplink)."""
        return cls(kind="concat", n_channels=n_channels)

    @classmethod
    def from_mode(cls, mode: str, *, tie_break: str = "all",
                  bits: int = 16, max_rounds: int = 3,
                  backend: str = "scan", p_miss=None) -> "Protocol":
        """Map a legacy ``fedocs.VALID_MODES`` string to a Protocol."""
        kind = _MODE_TO_KIND.get(mode)
        if kind is None:
            raise ValueError(
                f"unknown aggregation mode {mode!r}; "
                f"valid: {tuple(_MODE_TO_KIND)}")
        if mode == "max_q16":
            return cls.ideal_max(16, tie_break=tie_break)
        if mode == "max_q8":
            return cls.ideal_max(8, tie_break=tie_break)
        if mode == "max_noisy":
            return cls.ocs(bits=bits, p_miss=p_miss, max_rounds=max_rounds,
                           backend=backend)
        if mode == "max":
            return cls.max(bits=bits, tie_break=tie_break)
        return cls(kind=kind)

    # -- protocol state -----------------------------------------------------

    def with_p_miss(self, p_miss) -> "Protocol":
        """Bind (or rebind) the traced miss probability, e.g. one vmap lane."""
        return dataclasses.replace(self, p_miss=p_miss)

    def with_online(self, online) -> "Protocol":
        """Bind (or rebind) the worker-up mask — dark workers leave the
        contention entirely (``repro.faults`` dropout spans)."""
        return dataclasses.replace(self, online=online)

    # -- the aggregation law ------------------------------------------------

    def aggregate(self, h: jax.Array, rng: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, ProtocolAccounting]:
        """Pool a worker-leading feature tensor ``h: (N, ..., K)``.

        Returns ``(pooled, accounting)``.  The pooled value and its
        ``custom_vjp`` (winner-routed cotangent, paper Eq. 5-6) are
        bit-for-bit the historical ``fedocs`` aggregation laws; the
        accounting is the contention core's measured channel counters
        (zeros for the ideal kinds, which consume no simulated channel).

        ``kind="ocs"`` additionally needs ``rng`` (the per-sub-slot sensing
        key) and a bound ``p_miss``; both are ordinary traced values, so one
        compiled computation serves a whole miss-probability axis.
        Every operation it adds to a program sits under the
        ``protocol.aggregate`` name scope.
        """
        with jax.named_scope("protocol.aggregate"):
            return self._aggregate(h, rng)

    def _aggregate(self, h: jax.Array, rng: Optional[jax.Array]
                   ) -> Tuple[jax.Array, ProtocolAccounting]:
        if self.kind == "sum":
            return jnp.sum(h, axis=0), ProtocolAccounting.zeros()
        if self.kind == "max":
            return fedocs.maxpool(h, self.tie_break), ProtocolAccounting.zeros()
        if self.kind == "ideal_max":
            return (fedocs.maxpool_quantized(h, self.bits, self.tie_break),
                    ProtocolAccounting.zeros())
        if self.kind == "mean":
            return fedocs.meanpool(h), ProtocolAccounting.zeros()
        if self.kind == "concat":
            return fedocs.concat(h), ProtocolAccounting.zeros()
        # kind == "ocs"
        if rng is None:
            raise ValueError(
                "Protocol.ocs aggregation needs rng (the sensing PRNG key)")
        if self.p_miss is None:
            raise ValueError(
                "Protocol.ocs has no p_miss bound; construct with "
                "Protocol.ocs(bits, p_miss=...) or bind via with_p_miss()")
        p = jnp.asarray(self.p_miss, jnp.float32)
        online = (jnp.ones((h.shape[0],), bool) if self.online is None
                  else jnp.asarray(self.online, bool))
        return _ocs_pool(h, rng, p, online, self.bits, self.max_rounds,
                         self.backend)

    # -- derived protocol facts --------------------------------------------

    def output_dim(self, n_workers: int, k: int) -> int:
        """Fused feature width the head sees: N*K for concat, K otherwise."""
        return n_workers * k if self.kind == "concat" else k

    def resolved_payload_bits(self) -> int:
        """The single payload-bits source of truth for :meth:`comm_load`:
        the explicit override if set, else the D-bit code width for the
        quantized-payload kinds (ocs/ideal_max), else a full 32-bit float."""
        if self.payload_bits is not None:
            return self.payload_bits
        if self.kind in ("ocs", "ideal_max"):
            return self.bits
        return 32

    def comm_load(self, n_workers: int, k: int) -> channel.CommLoad:
        """Analytic per-round uplink/downlink accounting (paper §I / §IV).

        Consolidates the ``channel.ocs_load``/``concat_load``/``mean_load``
        helpers behind the protocol object: the payload width comes from
        :meth:`resolved_payload_bits` and ``n_channels`` from the protocol,
        so callers no longer re-derive a ``ChannelConfig`` ad hoc.
        """
        cfg = channel.ChannelConfig(payload_bits=self.resolved_payload_bits(),
                                    n_channels=self.n_channels)
        if self.kind in ("max", "ideal_max", "ocs"):
            return channel.ocs_load(n_workers, k, bits=self.bits, cfg=cfg)
        if self.kind in ("mean", "sum"):
            # every worker transmits every element; the server reduces
            return channel.mean_load(n_workers, k, cfg=cfg)
        return channel.concat_load(n_workers, k, cfg=cfg)


jax.tree_util.register_dataclass(
    Protocol,
    data_fields=["p_miss", "online"],
    meta_fields=["kind", "bits", "tie_break", "max_rounds", "backend",
                 "n_channels", "payload_bits"])

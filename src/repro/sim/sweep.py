"""Vectorized OCS scenario-grid runner.

Evaluates a full scenario grid — rounds x workers (padded/masked to a common
max-N) x ``p_miss`` x ``n_channels`` — in ONE compiled computation per
``bits`` value, instead of one Python dispatch per ``(N, K)`` round.  The
worker count and miss probability enter the batched protocol cores
(``repro.core.ocs.ocs_maxpool_core`` / ``ocs_maxpool_noisy_core``) as traced
values, so a grid with ``bits`` in {8, 16} costs exactly two compilations of
each engine no matter how many cells it has.  Compilations are observable via
:func:`trace_counts` (a counter bumped on every jit trace), which the
property tests and the benchmark smoke row assert on.

On multi-device hosts the scenario axis is additionally sharded over a 1-D
``("s",)`` mesh with ``shard_map`` (groups are padded up to a device-count
multiple; the padding rows are dropped before results are returned), so a
grid scales with hardware while staying bit-for-bit identical to the
single-device vmap path (property-tested with forced host devices).  Pass
``n_devices=1`` to force the plain vmap path.  The mesh/shard_map machinery
is shared with the curve engine's lane sharding via ``repro.sim.shard``.

The padded accounting is bit-for-bit identical to unpadded per-round calls
(``tests/test_sweep.py``), so ``benchmarks/bench_comm.py`` reproduces its
historical O(K)-vs-O(N*K) rows from one sweep.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import ocs
from repro.sim import shard as sim_shard
from repro.sim.scenarios import Scenario

# ---------------------------------------------------------------------------
# compilation observability
# ---------------------------------------------------------------------------

_TRACE = "sweep.trace."


def reset_trace_counts() -> None:
    """Zero the per-engine jit trace counters (used by tests/benchmarks)."""
    obs.reset(_TRACE)


def trace_counts() -> Dict[str, int]:
    """Number of times each sweep engine has been traced (== compiled).

    The counters are bumped by a Python side effect inside the jitted
    functions, which only executes while JAX traces — cache hits leave them
    untouched.
    """
    return obs.view(_TRACE, ("clean", "noisy"))


# ---------------------------------------------------------------------------
# jitted engines: vmap(rounds) o vmap(scenarios) over the batched cores,
# optionally shard_map-ped over the scenario axis on multi-device hosts
# ---------------------------------------------------------------------------

def _ceil_div(a: jax.Array, b: jax.Array) -> jax.Array:
    return (a + b - 1) // b


def _shard_scenarios(fn, n_devices: int, n_args: int):
    """Wrap an all-scenario-leading engine in shard_map over the ``s`` mesh."""
    return sim_shard.shard_1d(fn, n_devices,
                              in_specs=(P("s"),) * n_args, out_specs=P("s"))


@functools.partial(jax.jit,
                   static_argnames=("bits", "max_id_bits", "n_devices"))
def _sweep_clean(h, mask, id_bits, n_channels, *, bits, max_id_bits,
                 n_devices=1):
    """h: (S, R, N_max, K); mask: (S, N_max); id_bits/n_channels: (S,)."""
    obs.count(_TRACE + "clean")
    core = functools.partial(ocs.ocs_maxpool_core,
                             bits=bits, max_id_bits=max_id_bits)
    per_round = jax.vmap(core, in_axes=(0, None, None))
    engine = jax.vmap(per_round, in_axes=(0, 0, 0))
    if n_devices > 1:
        engine = _shard_scenarios(engine, n_devices, n_args=3)
    res = engine(h, mask, id_bits)
    latency = _ceil_div(res.contention_slots, n_channels[:, None])
    return res, latency


@functools.partial(jax.jit,
                   static_argnames=("bits", "max_id_bits", "max_rounds",
                                    "backend", "n_devices"))
def _sweep_noisy(h, mask, id_bits, rng, p_miss, n_channels, *,
                 bits, max_id_bits, max_rounds, backend="scan", n_devices=1):
    """As `_sweep_clean` plus rng: (S, R, 2) keys and p_miss: (S, N_max)
    per-worker miss probabilities, traced (homogeneous scenarios carry the
    scalar broadcast — bit-for-bit the historical scalar path).
    ``backend`` selects the contention engine (``Protocol.backend``:
    ``"scan"`` or the fused ``"pallas"`` kernel, bit-for-bit identical)."""
    obs.count(_TRACE + "noisy")
    core = functools.partial(ocs.ocs_maxpool_noisy_core, bits=bits,
                             max_id_bits=max_id_bits, max_rounds=max_rounds,
                             backend=backend)
    per_round = jax.vmap(core, in_axes=(0, None, None, 0, None))
    engine = jax.vmap(per_round, in_axes=(0, 0, 0, 0, 0))
    if n_devices > 1:
        engine = _shard_scenarios(engine, n_devices, n_args=5)
    res = engine(h, mask, id_bits, rng, p_miss)
    latency = _ceil_div(res.contention_slots, n_channels[:, None])
    return res, latency


# ---------------------------------------------------------------------------
# host-side packing + the public grid runner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepResult:
    """Stacked outcome of one grid sweep.

    Pytree leaves of ``clean``/``noisy`` carry leading (S, R) axes: scenario
    (in the order passed to :func:`run_sweep`) then aggregation round.
    ``h``/``mask`` are the padded inputs, kept so per-cell results can be
    cross-checked against unbatched oracles.
    """

    scenarios: List[Scenario]
    k_elems: int
    rounds: int
    n_max: int
    h: np.ndarray                                   # (S, R, N_max, K)
    mask: np.ndarray                                # (S, N_max)
    clean: Optional[ocs.OCSResult] = None           # leaves (S, R, ...)
    clean_latency_slots: Optional[np.ndarray] = None    # (S, R)
    noisy: Optional[ocs.NoisyOCSResult] = None      # leaves (S, R, ...)
    noisy_latency_slots: Optional[np.ndarray] = None    # (S, R)

    def scenario_h(self, i: int) -> np.ndarray:
        """Unpadded (R, n_workers, K) features of scenario ``i``."""
        return self.h[i, :, :self.scenarios[i].n_workers, :]

    def clean_cell(self, i: int, r: int = 0) -> ocs.OCSResult:
        return jax.tree.map(lambda x: x[i, r], self.clean)

    def noisy_cell(self, i: int, r: int = 0) -> ocs.NoisyOCSResult:
        return jax.tree.map(lambda x: x[i, r], self.noisy)


def _default_features(scenarios: Sequence[Scenario], rounds: int,
                      k_elems: int, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((rounds, s.n_workers, k_elems))
            .astype(np.float32) for s in scenarios]


def _scatter(groups):
    """Reassemble per-bits group pytrees into original scenario order."""
    order = np.concatenate([np.asarray(idx) for idx, _ in groups])
    cat = jax.tree.map(
        lambda *xs: np.concatenate([np.asarray(x) for x in xs], axis=0),
        *(tree for _, tree in groups))
    inv = np.argsort(order, kind="stable")
    return jax.tree.map(lambda x: x[inv], cat)


def run_sweep(scenarios: Sequence[Scenario], *,
              k_elems: int = 64,
              rounds: int = 1,
              seed: int = 0,
              h_by_scenario: Optional[Sequence[np.ndarray]] = None,
              rng_seed: int = 0,
              max_rounds: int = 3,
              backend: str = "scan",
              include_clean: bool = True,
              include_noisy: bool = True,
              n_devices: Optional[int] = None) -> SweepResult:
    """Evaluate every scenario x round cell in one dispatch per ``bits`` value.

    Args:
      scenarios:     grid cells (see ``repro.sim.scenarios``).
      k_elems:       K, feature elements per aggregation round.
      rounds:        R, independent aggregation rounds per scenario.
      seed:          feature-generation seed (ignored if ``h_by_scenario``).
      h_by_scenario: optional per-scenario features, each (R, n_workers, K) —
                     lets benchmarks replay an exact historical rng stream.
      rng_seed:      sensing-noise key seed for the noisy engine.
      max_rounds:    re-contention bound of the noisy protocol.
      backend:       contention engine of the noisy protocol
                     (``repro.protocol.Protocol.backend``: ``"scan"`` or
                     ``"pallas"``; bit-for-bit interchangeable).
      include_clean / include_noisy: which engines to run.  The noisy engine
                     subsumes clean behaviour at ``p_miss=0`` but reports the
                     collision/accuracy accounting instead of the blocking-tx
                     accounting.
      n_devices:     devices to shard the scenario axis over.  ``None`` (the
                     default) uses every local device; ``1`` forces the
                     single-device vmap path.  Results are identical either
                     way — sharding only changes placement.

    Returns:
      SweepResult with (S, R)-stacked pytrees, in the scenario order given.
    """
    scenarios = list(scenarios)
    if not scenarios:
        raise ValueError("run_sweep needs at least one scenario")
    if h_by_scenario is None:
        h_by_scenario = _default_features(scenarios, rounds, k_elems, seed)
    if len(h_by_scenario) != len(scenarios):
        raise ValueError("h_by_scenario must match scenarios 1:1")

    n_max = max(s.n_workers for s in scenarios)
    s_total = len(scenarios)
    h_pad = np.zeros((s_total, rounds, n_max, k_elems), dtype=np.float32)
    mask = np.zeros((s_total, n_max), dtype=bool)
    id_bits = np.zeros((s_total,), dtype=np.int32)
    # per-worker miss probabilities (padded rows are masked-out in the core,
    # so their p_miss entries are inert)
    p_miss = np.zeros((s_total, n_max), dtype=np.float32)
    n_channels = np.zeros((s_total,), dtype=np.int32)
    for i, (s, h) in enumerate(zip(scenarios, h_by_scenario)):
        h = np.asarray(h, dtype=np.float32)
        if h.shape != (rounds, s.n_workers, k_elems):
            raise ValueError(
                f"scenario {s.name!r}: h shape {h.shape} != "
                f"{(rounds, s.n_workers, k_elems)}")
        h_pad[i, :, :s.n_workers, :] = h
        mask[i, :s.n_workers] = True
        id_bits[i] = ocs.host_id_bits(s.n_workers)
        p_miss[i, :s.n_workers] = s.p_miss_per_worker()
        n_channels[i] = s.n_channels

    # independent noise keys per (scenario, round), stable under regrouping
    keys = jax.random.split(
        jax.random.PRNGKey(rng_seed), s_total * rounds
    ).reshape(s_total, rounds, -1)

    # group cells by the only static axis: the quantization depth
    by_bits: Dict[int, List[int]] = {}
    for i, s in enumerate(scenarios):
        by_bits.setdefault(s.bits, []).append(i)

    clean_groups, noisy_groups = [], []
    for bits, idx in sorted(by_bits.items()):
        sel = np.asarray(idx)
        # the scan-length bound (and its 32-bit-word guard) is per bits-group:
        # a global max over *all* scenarios would make a wide-bits cell raise
        # on the id_bits of an unrelated large-N narrow-bits cell.
        max_id_bits = int(id_bits[sel].max())
        n_dev = sim_shard.lane_devices(n_devices, len(sel))

        def dev_pad(x: np.ndarray) -> jax.Array:
            return jnp.asarray(sim_shard.pad_lanes(x, n_dev))

        def unpad(tree):
            return jax.tree.map(lambda x: np.asarray(x)[:len(sel)], tree)

        args = (dev_pad(h_pad[sel]), dev_pad(mask[sel]),
                dev_pad(id_bits[sel]))
        nch = dev_pad(n_channels[sel])
        if include_clean:
            res, lat = _sweep_clean(*args, nch, bits=bits,
                                    max_id_bits=max_id_bits, n_devices=n_dev)
            clean_groups.append((sel, unpad((res, lat))))
        if include_noisy:
            res, lat = _sweep_noisy(*args, dev_pad(keys[sel]),
                                    dev_pad(p_miss[sel]),
                                    nch, bits=bits, max_id_bits=max_id_bits,
                                    max_rounds=max_rounds, backend=backend,
                                    n_devices=n_dev)
            noisy_groups.append((sel, unpad((res, lat))))

    out = SweepResult(scenarios=scenarios, k_elems=k_elems, rounds=rounds,
                      n_max=n_max, h=h_pad, mask=mask)
    if clean_groups:
        out.clean, out.clean_latency_slots = _scatter(clean_groups)
    if noisy_groups:
        out.noisy, out.noisy_latency_slots = _scatter(noisy_groups)
    return out

"""Channel-in-the-loop training curves: accuracy vs channel quality.

This is the paper's actual end-to-end experiment, which the repo previously
validated only in halves: ``repro.sim.sweep`` measured protocol behaviour
while ``repro.train`` trained with ideal pooling.  Here the two meet — the
vertical learner's forward pass fuses embeddings through the *simulated* OCS
channel (``repro.protocol.Protocol.ocs``: quantized D-bit contention,
per-sub-slot miss detection, lowest-index capture), and short training runs
sweep the ``p_miss x bits`` scenario grid into accuracy-vs-p_miss and
accuracy-vs-bits tables (emitted by ``repro.sim.results``).

Compilation contract (mirrors the sweep engine): the protocol's ``p_miss``
leaf and the sensing rng are *traced* — the whole miss-probability axis
trains as ``vmap`` lanes of ONE compiled train step per ``bits`` value,
each lane carrying its own ``Protocol`` pytree (same static metadata, its
own ``p_miss`` leaf).  An ideal ``Protocol.ideal_max(bits)`` reference run
(same init, same data stream) trains alongside; the ``p_miss=0`` lane must
match it bit for bit, which ``benchmarks/bench_curves.py`` and
``tests/test_train_curves.py`` assert.

The fused on-device engine drives everything: the whole ``steps`` loop is
one ``lax.scan`` inside ONE jitted dispatch per ``bits`` value.  Batch
indices are drawn on device from a threaded PRNG key, the noisy lanes, the
ideal reference and the final channel-in-the-loop evaluation all run in
that single dispatch, and the logged losses accumulate into an on-device
``(lanes, n_logged)`` buffer fetched once at the end — no per-step dispatch
or host sync.  On multi-device hosts the ``p_miss`` lane axis is sharded
over a 1-D mesh via ``repro.sim.shard`` (vmap fallback on one device,
bit-for-bit identical either way).  (The legacy per-step ``engine="python"``
driver was removed after its one-release parity window — the scan engine
had been property-tested bit-for-bit against it since it landed.)

:func:`run_scheduled_curves` additionally threads a
``repro.protocol.BitsSchedule`` through the same fused scan: one compiled
training-step branch per candidate depth, ``lax.switch``-ed per round by
the schedule's pure on-device policy consuming the protocol accounting
(collision/round telemetry) of the previous round — channel-aware backoff
depth scheduling in ONE host dispatch for the whole run.

:func:`run_curves_dp` is the 2-D generalization: p_miss lanes x
data-parallel batch shards, with each rank's top-k-sparsified gradients
(``repro.optim.compressed_allreduce.CompressedAllReduce``, error feedback
carried through the scan) all-reduced over the ``"d"`` axis *inside* the
fused scan and the DP payload bits measured from actual kept-element
counts — the complement of the uplink accounting, reported together by
``repro.sim.results.summarize_dp_curves``.  The DP axis runs on a 2-D mesh
(``repro.sim.shard.mesh_2d``) when devices allow, else on a named vmap
axis, bit-for-bit identical either way.

Compilations are observable via :func:`trace_counts`, host dispatches via
:func:`dispatch_counts` — the fused engine costs ONE dispatch per ``bits``
value (``fused``; ``fused_dp`` for the 2-D engine), a scheduled run ONE
dispatch total (``sched``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import vertical
from repro.core.vertical import VerticalConfig
from repro.data.vertical_data import PatchTaskConfig, patch_classification
from repro.optim import optimizers, schedules
from repro.optim.compressed_allreduce import CompressedAllReduce
from repro.protocol import BitsSchedule, Protocol
from repro.sim import shard as sim_shard
from repro.train.train_step import make_train_step

# ---------------------------------------------------------------------------
# compilation + dispatch observability (same contract as repro.sim.sweep)
# ---------------------------------------------------------------------------

_COUNTER_KEYS = ("fused", "sched", "fused_dp", "fused_faults")
_TRACE = "curves.trace."
_DISPATCH = "curves.dispatch."


def reset_trace_counts() -> None:
    """Zero the per-engine jit trace counters (used by tests/benchmarks)."""
    obs.reset(_TRACE)


def trace_counts() -> Dict[str, int]:
    """Times each curve engine has been traced.  One :func:`run_curves`
    costs exactly one ``fused`` trace per ``bits`` value (one ``sched``
    trace per :func:`run_scheduled_curves`), no matter how many ``p_miss``
    lanes the grid has."""
    return obs.view(_TRACE, _COUNTER_KEYS)


def reset_dispatch_counts() -> None:
    """Zero the per-engine host-dispatch counters."""
    obs.reset(_DISPATCH)


def dispatch_counts() -> Dict[str, int]:
    """Jitted-engine dispatches issued from the host by each curve driver.

    The fused engine issues ONE ``fused`` dispatch per ``bits`` value
    (train loop + ideal reference + eval, all on device); a scheduled run
    issues ONE ``sched`` dispatch for the whole training run, every
    candidate depth included.  ``benchmarks/bench_curves.py`` asserts the
    ``<= ceil(steps/log_every) + 2`` per-bits bound, guarding the fused
    call structure against falling back to per-step driving.
    """
    return obs.view(_DISPATCH, _COUNTER_KEYS)


# ---------------------------------------------------------------------------
# configuration + result containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CurveConfig:
    """One accuracy-vs-channel-quality experiment grid.

    ``p_miss`` lanes are scalars (every worker senses equally) or length-
    ``n_workers`` sequences (heterogeneous near/far users, e.g. from
    ``repro.sim.scenarios.near_far_p_miss``); lanes may mix both — scalars
    broadcast.  ``backend`` picks the noisy-contention engine of the
    channel-in-the-loop forward pass (``"scan"`` or the fused ``"pallas"``
    kernel; bit-for-bit interchangeable) — it becomes the static
    ``Protocol.backend`` of every lane's protocol object.
    """

    bits: Sequence[int] = (8, 16)        # backoff/payload depth axis (static)
    p_miss: Sequence = (0.0, 0.02, 0.05, 0.1)          # traced lane axis
    steps: int = 60
    batch: int = 64
    lr: float = 3e-3
    max_rounds: int = 3                  # noisy re-contention bound
    n_train: int = 2048
    n_val: int = 512
    n_classes: int = 4
    grid: int = 2                        # grid^2 workers (paper §IV-B)
    hw: int = 16                         # image side (patch_dim = (hw/grid)^2)
    sigma: float = 0.5
    encoder_dims: Sequence[int] = (32,)
    embed_dim: int = 16                  # K — transmitted feature width
    head_dims: Sequence[int] = (32,)
    seed: int = 0
    log_every: int = 10
    backend: str = "scan"                # noisy-contention engine
    dp_shards: int = 1                   # data-parallel batch shards
    #   (run_curves_dp: each rank trains batch/dp_shards samples and the
    #   compressed gradients all-reduce inside the fused scan)

    def __post_init__(self):
        if self.dp_shards < 1:
            raise ValueError(f"dp_shards must be >= 1, got {self.dp_shards}")
        if self.batch % self.dp_shards:
            raise ValueError(
                f"batch={self.batch} must divide evenly into "
                f"dp_shards={self.dp_shards} ranks")
        for b in self.bits:
            if b not in (8, 16):
                raise ValueError(
                    f"bits={b}: the ideal reference run needs a "
                    "Protocol.ideal_max(bits) aggregation (8 or 16)")
        if not self.p_miss:
            raise ValueError("p_miss needs at least one lane")
        for p in self.p_miss:
            arr = np.asarray(p, np.float64)
            if arr.ndim not in (0, 1):
                raise ValueError(f"p_miss lane must be scalar or "
                                 f"per-worker, got shape {arr.shape}")
            if arr.ndim == 1 and arr.shape[0] != self.n_workers:
                raise ValueError(
                    f"per-worker p_miss lane needs {self.n_workers} "
                    f"entries, got {arr.shape[0]}")
            if not np.all((0.0 <= arr) & (arr < 1.0)):
                raise ValueError(
                    f"p_miss lanes must be in [0, 1): {self.p_miss}")

    @property
    def n_workers(self) -> int:
        return self.grid * self.grid

    def protocol(self, bits: int) -> Protocol:
        """The (p_miss-unbound) OCS protocol template of one ``bits`` cell."""
        return Protocol.ocs(bits=bits, max_rounds=self.max_rounds,
                            backend=self.backend)

    def lane_p_miss(self, dtype=np.float32) -> np.ndarray:
        """Lane axis as an array: (L,) if all lanes are scalar, else the
        per-worker broadcast (L, n_workers)."""
        if all(np.ndim(p) == 0 for p in self.p_miss):
            return np.asarray(self.p_miss, dtype)
        return np.stack([
            np.broadcast_to(np.asarray(p, dtype), (self.n_workers,))
            for p in self.p_miss])

    def logged_steps(self) -> List[int]:
        """Steps whose train loss lands in ``CurveResult.loss_history``."""
        return sorted(set(range(0, self.steps, self.log_every))
                      | {self.steps - 1})


@dataclasses.dataclass
class CurveResult:
    """Stacked outcome of one curve grid.

    Lane axis L == ``len(config.p_miss)``; bits axis follows
    ``config.bits`` order.  ``*_ideal`` rows come from the reference run
    with ideal ``Protocol.ideal_max(bits)`` pooling (a single vmap lane —
    the ideal run is deterministic and lane-independent).  ``p_miss`` is
    the float32 lane array the engine traces (``config.lane_p_miss()``), so
    the reported operating points are exactly the compiled ones.
    """

    config: CurveConfig
    p_miss: np.ndarray                  # (L,) or (L, N) per-worker lanes
    acc: np.ndarray                     # (n_bits, L) channel-in-the-loop
    nll: np.ndarray                     # (n_bits, L)
    acc_ideal: np.ndarray               # (n_bits,)
    nll_ideal: np.ndarray               # (n_bits,)
    loss_history: np.ndarray            # (n_bits, n_logged, L)
    ideal_loss_history: np.ndarray      # (n_bits, n_logged)
    logged_steps: np.ndarray            # (n_logged,)
    noisy_params: List                  # per-bits lane-stacked trained params
    ideal_params: List                  # per-bits lane-stacked trained params


@dataclasses.dataclass
class ScheduledCurveResult:
    """Outcome of one ``BitsSchedule``-driven curve run.

    The schedule picks one candidate depth per training round from the
    previous round's protocol accounting; ``bits_per_step`` records the
    depth every step actually trained with (``bits_per_step[0]`` is always
    ``schedule.candidates[schedule.init_index]``).  ``collision_frac`` is
    the lane-mean collision fraction at the logged steps — the telemetry
    the policy consumed.
    """

    config: CurveConfig
    schedule: BitsSchedule
    p_miss: np.ndarray                  # (L,) or (L, N)
    acc: np.ndarray                     # (L,) channel-in-the-loop eval
    nll: np.ndarray                     # (L,)
    loss_history: np.ndarray            # (n_logged, L)
    collision_frac: np.ndarray          # (n_logged,)
    bits_per_step: np.ndarray           # (steps,) chosen depth per round
    logged_steps: np.ndarray            # (n_logged,)
    params: object                      # lane-stacked trained params


@dataclasses.dataclass
class FaultCurveResult:
    """Outcome of one fault-injection curve grid (``run_fault_curves``).

    The lane axis L indexes ``fault_lanes`` — one ``repro.faults.FaultModel``
    per lane, all sharing one (static) ``DegradePolicy`` so the whole grid
    compiles once.  Degradation telemetry rides beside accuracy:
    ``stale_age`` is the staleness (frames since the last resolved frame) at
    the logged steps, and the ``*_frames``/``retry_slots`` arrays are whole-
    run totals billed by ``FaultAccounting``.
    """

    config: CurveConfig
    fault_lanes: Sequence               # the FaultModel lanes, as given
    acc: np.ndarray                     # (n_bits, L) channel-in-the-loop
    nll: np.ndarray                     # (n_bits, L)
    loss_history: np.ndarray            # (n_bits, n_logged, L)
    stale_age: np.ndarray               # (n_bits, n_logged, L) int64
    dropped_frames: np.ndarray          # (n_bits, L) int64 run totals
    outage_frames: np.ndarray           # (n_bits, L) int64 run totals
    retry_slots: np.ndarray             # (n_bits, L) int64 run totals
    logged_steps: np.ndarray            # (n_logged,)
    params: List                        # per-bits lane-stacked trained params


@dataclasses.dataclass
class DPCurveResult:
    """Outcome of one 2-D (p_miss lanes x DP shards) compressed-comms run.

    The DP payload numbers are MEASURED inside the fused scan — per step,
    the kept-element counts of every rank's exact-k masks are billed through
    ``CompressedAllReduce.reduce``'s :class:`DPAccounting` and psum'd over
    ranks.  ``dp_payload_bits_step`` / ``dp_dense_bits_step`` are the
    analytic per-step totals (all ranks) the measurement must equal — the
    tie-exact ``topk_mask`` guarantees it, and ``tests/test_dp_curves.py``
    asserts it.
    """

    config: CurveConfig
    compress: CompressedAllReduce
    p_miss: np.ndarray                  # (L,) or (L, N) per-worker lanes
    acc: np.ndarray                     # (n_bits, L) channel-in-the-loop
    nll: np.ndarray                     # (n_bits, L)
    loss_history: np.ndarray            # (n_bits, n_logged, L) rank-mean loss
    dp_payload_bits: np.ndarray         # (n_bits, n_logged, L) measured/step
    dp_payload_bits_total: np.ndarray   # (n_bits, L) int64, whole run
    dp_payload_bits_step: int           # analytic bits/step, all ranks
    dp_dense_bits_step: int             # uncompressed bits/step, all ranks
    logged_steps: np.ndarray            # (n_logged,)
    params: List                        # per-bits lane-stacked trained params


# ---------------------------------------------------------------------------
# shared engine pieces: data/key streams, losses, per-bits train steps
# ---------------------------------------------------------------------------

def _lane_stack(tree, lanes: int):
    """Add a leading lane axis without materializing per-lane host copies."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (lanes,) + jnp.shape(x)), tree)


def _vertical_config(ccfg: CurveConfig, bits: int, noisy: bool
                     ) -> VerticalConfig:
    patch_dim = (ccfg.hw // ccfg.grid) ** 2
    # the OCS winner is the lowest-indexed max-code holder, so the ideal
    # reference must route gradients the same way (tie_break="first")
    proto = (ccfg.protocol(bits) if noisy
             else Protocol.ideal_max(bits, tie_break="first"))
    return VerticalConfig(
        n_workers=ccfg.n_workers, input_dim=patch_dim,
        encoder_dims=tuple(ccfg.encoder_dims), embed_dim=ccfg.embed_dim,
        head_dims=tuple(ccfg.head_dims), output_dim=ccfg.n_classes,
        task="classification", aggregation=proto)


def _stream_keys(ccfg: CurveConfig, bits: int):
    """Root keys of the batch and sensing streams of one ``bits`` cell.

    Every stochastic input derives from these by fixed formulas —
    ``_batch_indices(k_data, step)`` for the shared batch stream,
    ``fold_in(lane_keys[l], step)`` for lane ``l``'s per-step sensing key
    (``step == steps`` is the held-out evaluation key) — so runs are
    reproducible and a scheduled run whose schedule never switches away
    from depth ``bits`` trains bit-for-bit the plain ``run_curves``
    trajectory of that depth.
    """
    base = jax.random.PRNGKey(ccfg.seed + 7919 * bits)
    k_data, k_noise = jax.random.split(base)
    lane_keys = jax.random.split(k_noise, len(ccfg.p_miss))
    return k_data, lane_keys


def _batch_indices(k_data, step, batch: int, n_train: int):
    """On-device minibatch draw: a pure function of (k_data, step)."""
    return jax.random.randint(jax.random.fold_in(k_data, step),
                              (batch,), 0, n_train)


def _fold_lanes(lane_keys, step):
    """Per-lane sensing keys for one step: fold the step into every lane."""
    return jax.vmap(jax.random.fold_in, in_axes=(0, None))(lane_keys, step)


def _make_data(ccfg: CurveConfig):
    task = PatchTaskConfig(n_classes=ccfg.n_classes, grid=ccfg.grid,
                           hw=ccfg.hw, sigma=ccfg.sigma)
    views, labels = patch_classification(task, ccfg.n_train, seed=ccfg.seed)
    v_views, v_labels = patch_classification(task, ccfg.n_val,
                                             seed=ccfg.seed + 1)
    return (jnp.asarray(views), jnp.asarray(labels),
            jnp.asarray(v_views), jnp.asarray(v_labels))


def _make_steps(ccfg: CurveConfig, bits: int):
    """Per-bits vertical configs, optimizer, and train-step closures.

    The noisy loss takes the channel state as ``chan = (rng, protocol)`` —
    the per-lane sensing key plus the lane's ``Protocol`` pytree (its
    ``p_miss`` leaf is the only traced difference between lanes).
    """
    vcfg_n = _vertical_config(ccfg, bits, noisy=True)
    vcfg_i = _vertical_config(ccfg, bits, noisy=False)

    def noisy_loss(values, batch, chan, _cfg=vcfg_n):
        bviews, blabels = batch
        rng, proto = chan
        return vertical.loss_fn(_cfg, values, bviews, blabels, rng=rng,
                                protocol=proto)

    def ideal_loss(values, batch, _cfg=vcfg_i):
        bviews, blabels = batch
        return vertical.loss_fn(_cfg, values, bviews, blabels)

    warmup = max(1, ccfg.steps // 10)
    opt = optimizers.adamw(
        schedules.linear_warmup_cosine(ccfg.lr, warmup, ccfg.steps),
        weight_decay=0.01)
    step_n = make_train_step(noisy_loss, opt, with_rng=True)
    step_i = make_train_step(ideal_loss, opt)
    return vcfg_n, vcfg_i, opt, step_n, step_i


def _log_slots(ccfg: CurveConfig, logged: List[int]) -> np.ndarray:
    """(steps,) map step -> loss_history slot; unlogged steps point one past
    the buffer and are dropped by the scatter's ``mode="drop"``."""
    slots = np.full((ccfg.steps,), len(logged), np.int32)
    for i, s in enumerate(logged):
        slots[s] = i
    return slots


# ---------------------------------------------------------------------------
# the fused on-device engine: the whole curve run is one dispatch per bits
# ---------------------------------------------------------------------------

def _make_fused(ccfg: CurveConfig, per_bits, n_logged: int, n_dev: int):
    """Build the jitted fused engine for one ``bits`` value.

    ``per_bits`` is that value's ``_make_steps`` tuple (shared with the
    caller, which needs its optimizer for the init).  One dispatch runs:
    the ``lax.scan`` over all training steps (noisy lanes vmapped over the
    traced ``(rng, Protocol)`` channel state, batch indices drawn on
    device), the single-lane ideal reference scan, and both
    channel-in-the-loop evaluations.  Logged losses accumulate in carried
    on-device buffers (scattered by the precomputed step->slot map), so
    nothing syncs to the host until the caller fetches the results.  With
    ``n_dev > 1`` the lane axis runs under ``shard_map`` (lane-leading args
    sharded, data/keys replicated) — bit-for-bit the vmap path, as with
    ``run_sweep``.
    """
    vcfg_n, vcfg_i, _opt, step_n, step_i = per_bits
    proto_tmpl = vcfg_n.resolve_protocol()
    steps, batch, n_train = ccfg.steps, ccfg.batch, ccfg.n_train

    def scan_lanes(step_fn, vals, opts, hist, k_data, views, labels, slots):
        """Shared steps-scan: train ``vals`` lanes, scatter logged losses."""
        def body(carry, x):
            vals, opts, hist = carry
            step, slot = x
            idx = _batch_indices(k_data, step, batch, n_train)
            b = (views[:, idx], labels[idx])
            vals, opts, met = step_fn(vals, opts, b, step)
            hist = hist.at[:, slot].set(met["loss_mean"], mode="drop")
            return (vals, opts, hist), None

        (vals, opts, hist), _ = jax.lax.scan(
            body, (vals, opts, hist),
            (jnp.arange(steps, dtype=jnp.int32), slots))
        return vals, opts, hist

    def noisy_lanes(params0, opt0, lane_keys, p, k_data, views, labels,
                    vviews, vlabels, slots):
        lanes = lane_keys.shape[0]          # shard-local lane count
        vals, opts = _lane_stack(params0, lanes), _lane_stack(opt0, lanes)
        hist = jnp.zeros((lanes, n_logged), jnp.float32)

        def step_fn(vals, opts, b, step):
            chan = (_fold_lanes(lane_keys, step), proto_tmpl.with_p_miss(p))
            return jax.vmap(step_n, in_axes=(0, 0, None, (0, 0)))(
                vals, opts, b, chan)

        vals, _opts, hist = scan_lanes(step_fn, vals, opts, hist,
                                       k_data, views, labels, slots)
        eval_chan = (_fold_lanes(lane_keys, steps), proto_tmpl.with_p_miss(p))
        met = jax.vmap(
            lambda v, ch: vertical.loss_fn(vcfg_n, v, vviews, vlabels,
                                           rng=ch[0], protocol=ch[1])[1],
            in_axes=(0, (0, 0)))(vals, eval_chan)
        return vals, hist, met["acc"], met["nll"]

    def ideal_lanes(params0, opt0, k_data, views, labels, vviews, vlabels,
                    slots):
        vals, opts = _lane_stack(params0, 1), _lane_stack(opt0, 1)
        hist = jnp.zeros((1, n_logged), jnp.float32)

        def step_fn(vals, opts, b, step):
            return jax.vmap(step_i, in_axes=(0, 0, None))(vals, opts, b)

        vals, _opts, hist = scan_lanes(step_fn, vals, opts, hist,
                                       k_data, views, labels, slots)
        met = jax.vmap(
            lambda v: vertical.loss_fn(vcfg_i, v, vviews, vlabels)[1])(vals)
        return vals, hist, met["acc"], met["nll"]

    noisy_engine = noisy_lanes
    if n_dev > 1:
        noisy_engine = sim_shard.shard_1d(
            noisy_lanes, n_dev,
            in_specs=(P(), P(), P("s"), P("s"), P(), P(), P(), P(), P(),
                      P()),
            out_specs=(P("s"), P("s"), P("s"), P("s")))

    def fused(params0, opt0, lane_keys, p, k_data, views, labels, vviews,
              vlabels, slots):
        obs.count(_TRACE + "fused")
        n_out = noisy_engine(params0, opt0, lane_keys, p, k_data, views,
                             labels, vviews, vlabels, slots)
        i_out = ideal_lanes(params0, opt0, k_data, views, labels, vviews,
                            vlabels, slots)
        return n_out, i_out

    return jax.jit(fused)


def _run_curves_scan(ccfg: CurveConfig, n_devices) -> CurveResult:
    lanes = len(ccfg.p_miss)
    p_lanes = ccfg.lane_p_miss()                 # float32 (L,) or (L, N)
    n_dev = sim_shard.lane_devices(n_devices, lanes)
    p_pad = jnp.asarray(sim_shard.pad_lanes(p_lanes, n_dev))

    views_j, labels_j, vv_j, vl_j = _make_data(ccfg)
    logged = ccfg.logged_steps()
    slots = jnp.asarray(_log_slots(ccfg, logged))

    acc = np.zeros((len(ccfg.bits), lanes), np.float64)
    nll = np.zeros_like(acc)
    acc_ideal = np.zeros((len(ccfg.bits),), np.float64)
    nll_ideal = np.zeros_like(acc_ideal)
    hist = np.zeros((len(ccfg.bits), len(logged), lanes), np.float64)
    hist_ideal = np.zeros((len(ccfg.bits), len(logged)), np.float64)
    noisy_params_out, ideal_params_out = [], []

    for bi, bits in enumerate(ccfg.bits):
        per_bits = _make_steps(ccfg, bits)
        vcfg_n, opt = per_bits[0], per_bits[2]
        k_data, lane_keys = _stream_keys(ccfg, bits)
        keys_pad = jnp.asarray(
            sim_shard.pad_lanes(np.asarray(lane_keys), n_dev))

        # identical init + identical batch stream for noisy lanes and the
        # ideal reference: any divergence is the channel's doing
        params0 = vertical.init(vcfg_n, jax.random.PRNGKey(ccfg.seed))
        opt0 = opt.init(params0)

        fused = _make_fused(ccfg, per_bits, len(logged), n_dev)
        obs.count(_DISPATCH + "fused")
        n_out, i_out = fused(params0, opt0, keys_pad, p_pad, k_data,
                             views_j, labels_j, vv_j, vl_j, slots)
        vals_n, hist_n, acc_n, nll_n = n_out
        vals_i, hist_i, acc_i, nll_i = i_out

        # results come back to the host only here, after the single fused
        # dispatch — no per-step sync anywhere above
        acc[bi] = np.asarray(acc_n)[:lanes]
        nll[bi] = np.asarray(nll_n)[:lanes]
        acc_ideal[bi] = float(np.asarray(acc_i)[0])
        nll_ideal[bi] = float(np.asarray(nll_i)[0])
        hist[bi] = np.asarray(hist_n)[:lanes].T
        hist_ideal[bi] = np.asarray(hist_i)[0]
        noisy_params_out.append(
            jax.tree.map(lambda x: x[:lanes], vals_n))
        ideal_params_out.append(vals_i)

    return CurveResult(
        config=ccfg, p_miss=ccfg.lane_p_miss(),
        acc=acc, nll=nll, acc_ideal=acc_ideal, nll_ideal=nll_ideal,
        loss_history=hist, ideal_loss_history=hist_ideal,
        logged_steps=np.asarray(logged), noisy_params=noisy_params_out,
        ideal_params=ideal_params_out)


# ---------------------------------------------------------------------------
# the public runners
# ---------------------------------------------------------------------------

def run_curves(ccfg: Optional[CurveConfig] = None, *,
               n_devices: Optional[int] = None) -> CurveResult:
    """Train the p_miss lane axis through the simulated channel, per bits.

    ``ccfg=None`` runs the default :class:`CurveConfig` grid.

    For every ``bits`` value: ONE compiled train step (lane-vmapped over
    the traced ``(rng, Protocol)`` channel state) trains all
    miss-probability lanes simultaneously from identical inits on an
    identical batch stream, and one ideal ``Protocol.ideal_max(bits)``
    reference trains beside it.  Evaluation runs channel-in-the-loop as
    well (fresh sensing keys, same ``p_miss`` lanes).  The whole run is
    ONE host dispatch per ``bits`` value.

    ``n_devices`` shards the ``p_miss`` lane axis over local devices.
    ``None`` (the default) uses every local device; ``1`` forces the
    single-device vmap path.  Results are identical either way — sharding
    only changes placement (lanes are padded up to a device-count multiple
    and the padding is dropped before results are returned).
    """
    return _run_curves_scan(ccfg if ccfg is not None else CurveConfig(),
                            n_devices)


# ---------------------------------------------------------------------------
# the fault engine: FaultModel lanes inside the fused scan, one dispatch
# ---------------------------------------------------------------------------

def _fault_stream_keys(ccfg: CurveConfig, bits: int, lanes: int):
    """Same key-derivation formula as :func:`_stream_keys`, lane count from
    the fault grid: with ``lanes == len(ccfg.p_miss)`` the streams are
    bitwise identical, which is what makes an ``FaultModel.iid(p)`` lane
    reproduce the corresponding :func:`run_curves` noisy lane bit for bit
    (property-tested in ``tests/test_faults.py``)."""
    base = jax.random.PRNGKey(ccfg.seed + 7919 * bits)
    k_data, k_noise = jax.random.split(base)
    lane_keys = jax.random.split(k_noise, lanes)
    return k_data, lane_keys


def _make_fault_steps(ccfg: CurveConfig, bits: int):
    """Per-bits config, optimizer and fault-aware train step.

    The channel state is ``chan = (rng, protocol, fault, fault_state)`` —
    the protocol template carries only static contention metadata (its
    ``p_miss``/``online`` leaves stay ``None``; the fault model supersedes
    them), and the evolved ``FaultState`` comes back through the metrics
    (``metrics["fault_state"]``) to be re-carried by the engine's scan.
    """
    vcfg_n = _vertical_config(ccfg, bits, noisy=True)

    def fault_loss(values, batch, chan, _cfg=vcfg_n):
        bviews, blabels = batch
        rng, proto, fm, fs = chan
        return vertical.loss_fn(_cfg, values, bviews, blabels, rng=rng,
                                protocol=proto, fault=fm, fault_state=fs)

    warmup = max(1, ccfg.steps // 10)
    opt = optimizers.adamw(
        schedules.linear_warmup_cosine(ccfg.lr, warmup, ccfg.steps),
        weight_decay=0.01)
    step_f = make_train_step(fault_loss, opt, with_rng=True)
    return vcfg_n, opt, step_f


def _make_fused_faults(ccfg: CurveConfig, per_bits, n_logged: int):
    """Build the jitted fault engine for one ``bits`` value.

    Same one-dispatch shape as :func:`_make_fused`: the whole ``steps``
    loop is one ``lax.scan``, the fault lanes are vmapped over the stacked
    ``FaultModel`` leaves and the carried per-lane ``FaultState`` (Markov
    burst/dropout chains persist across rounds *through the scan carry*),
    and the degradation telemetry accumulates on device beside the loss
    history.  Evaluation runs channel-in-the-loop under the final chain
    state with a fresh eval-shaped stale cache.
    """
    from repro import faults

    vcfg_n, _opt, step_f = per_bits
    proto_tmpl = vcfg_n.resolve_protocol()
    steps, batch, n_train = ccfg.steps, ccfg.batch, ccfg.n_train

    def fault_lanes_fn(params0, opt0, lane_keys, fm, fs0, k_data, views,
                       labels, vviews, vlabels, slots):
        lanes = lane_keys.shape[0]
        vals, opts = _lane_stack(params0, lanes), _lane_stack(opt0, lanes)
        hist = jnp.zeros((lanes, n_logged), jnp.float32)
        stale_hist = jnp.zeros((lanes, n_logged), jnp.int32)
        drop_tot = jnp.zeros((lanes,), jnp.int32)
        outage_tot = jnp.zeros((lanes,), jnp.int32)
        retry_tot = jnp.zeros((lanes,), jnp.int32)

        def body(carry, x):
            (vals, opts, fs, hist, stale_hist, drop_tot, outage_tot,
             retry_tot) = carry
            step, slot = x
            idx = _batch_indices(k_data, step, batch, n_train)
            b = (views[:, idx], labels[idx])
            chan = (_fold_lanes(lane_keys, step), proto_tmpl, fm, fs)
            vals, opts, met = jax.vmap(
                step_f, in_axes=(0, 0, None, (0, None, 0, 0)))(
                    vals, opts, b, chan)
            met = dict(met)
            fs = met.pop("fault_state")
            hist = hist.at[:, slot].set(met["loss_mean"], mode="drop")
            stale_hist = stale_hist.at[:, slot].set(met["fault_stale_age"],
                                                    mode="drop")
            drop_tot = drop_tot + met["fault_dropped_frames"]
            outage_tot = outage_tot + met["fault_outage"]
            retry_tot = retry_tot + met["fault_retry_slots"]
            return (vals, opts, fs, hist, stale_hist, drop_tot, outage_tot,
                    retry_tot), None

        (vals, _opts, fs, hist, stale_hist, drop_tot, outage_tot,
         retry_tot), _ = jax.lax.scan(
            body, (vals, opts, fs0, hist, stale_hist, drop_tot, outage_tot,
                   retry_tot),
            (jnp.arange(steps, dtype=jnp.int32), slots))

        # evaluate under the final chain state (bursts/outages carry over)
        # with a fresh eval-batch-shaped stale cache
        n_val = vviews.shape[1]
        eval_fs = faults.FaultState(
            bad=fs.bad, offline=fs.offline,
            stale=jnp.zeros((lanes, n_val, ccfg.embed_dim), jnp.float32),
            age=jnp.zeros((lanes,), jnp.int32),
            consec=jnp.zeros((lanes,), jnp.int32))
        met = jax.vmap(
            lambda v, r, fm_l, fs_l: vertical.loss_fn(
                vcfg_n, v, vviews, vlabels, rng=r, protocol=proto_tmpl,
                fault=fm_l, fault_state=fs_l)[1],
            in_axes=(0, 0, 0, 0))(
                vals, _fold_lanes(lane_keys, steps), fm, eval_fs)
        return (vals, hist, stale_hist, drop_tot, outage_tot, retry_tot,
                met["acc"], met["nll"])

    def fused(params0, opt0, lane_keys, fm, fs0, k_data, views, labels,
              vviews, vlabels, slots):
        obs.count(_TRACE + "fused_faults")
        return fault_lanes_fn(params0, opt0, lane_keys, fm, fs0, k_data,
                              views, labels, vviews, vlabels, slots)

    return jax.jit(fused)


def run_fault_curves(ccfg: CurveConfig, fault_lanes: Sequence
                     ) -> FaultCurveResult:
    """Train a grid of channel-fault lanes through the fused engine.

    ``fault_lanes`` is a sequence of ``repro.faults.FaultModel`` values —
    e.g. a burst-length sweep — all sharing one ``DegradePolicy`` (the
    policy is static metadata; mixed policies would need one compile each,
    so they are rejected — run one grid per policy instead).  Every fault
    parameter is a traced leaf: the whole grid trains as vmap lanes of ONE
    compiled dispatch per ``bits`` value (``trace_counts()["fused_faults"]``
    stays at one per bits no matter how many lanes), the same contract as
    :func:`run_curves`.

    Stream derivation matches :func:`run_curves` (see
    :func:`_fault_stream_keys`): with ``len(fault_lanes) ==
    len(ccfg.p_miss)``, an ``FaultModel.iid(p)`` lane trains bit-for-bit
    the ``run_curves`` noisy lane of the same ``p``.  Runs single-device
    (vmap lanes; lane sharding can follow the ``_make_fused`` pattern when
    fault grids outgrow one device).
    """
    from repro import faults

    lanes = len(fault_lanes)
    if lanes == 0:
        raise ValueError("fault_lanes needs at least one FaultModel")
    policies = {fm.policy for fm in fault_lanes}
    if len(policies) != 1:
        raise ValueError(
            f"all fault lanes must share one DegradePolicy (static "
            f"metadata — one compile per policy), got {policies}")
    fm_stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *fault_lanes)

    views_j, labels_j, vv_j, vl_j = _make_data(ccfg)
    logged = ccfg.logged_steps()
    slots = jnp.asarray(_log_slots(ccfg, logged))

    acc = np.zeros((len(ccfg.bits), lanes), np.float64)
    nll = np.zeros_like(acc)
    hist = np.zeros((len(ccfg.bits), len(logged), lanes), np.float64)
    stale = np.zeros((len(ccfg.bits), len(logged), lanes), np.int64)
    dropped = np.zeros((len(ccfg.bits), lanes), np.int64)
    outages = np.zeros_like(dropped)
    retries = np.zeros_like(dropped)
    params_out = []

    for bi, bits in enumerate(ccfg.bits):
        per_bits = _make_fault_steps(ccfg, bits)
        vcfg_n, opt = per_bits[0], per_bits[1]
        k_data, lane_keys = _fault_stream_keys(ccfg, bits, lanes)

        params0 = vertical.init(vcfg_n, jax.random.PRNGKey(ccfg.seed))
        opt0 = opt.init(params0)
        fs0 = _lane_stack(
            faults.init_state(ccfg.n_workers,
                              (ccfg.batch, ccfg.embed_dim)), lanes)

        fused = _make_fused_faults(ccfg, per_bits, len(logged))
        obs.count(_DISPATCH + "fused_faults")
        (vals, hist_b, stale_b, drop_b, out_b, retry_b, acc_b,
         nll_b) = fused(params0, opt0, jnp.asarray(lane_keys), fm_stacked,
                        fs0, k_data, views_j, labels_j, vv_j, vl_j, slots)

        acc[bi] = np.asarray(acc_b)
        nll[bi] = np.asarray(nll_b)
        hist[bi] = np.asarray(hist_b).T
        stale[bi] = np.asarray(stale_b, np.int64).T
        dropped[bi] = np.asarray(drop_b, np.int64)
        outages[bi] = np.asarray(out_b, np.int64)
        retries[bi] = np.asarray(retry_b, np.int64)
        params_out.append(vals)

    return FaultCurveResult(
        config=ccfg, fault_lanes=tuple(fault_lanes),
        acc=acc, nll=nll, loss_history=hist, stale_age=stale,
        dropped_frames=dropped, outage_frames=outages, retry_slots=retries,
        logged_steps=np.asarray(logged), params=params_out)


# ---------------------------------------------------------------------------
# the scheduled engine: BitsSchedule inside the fused scan, one dispatch
# ---------------------------------------------------------------------------

def _make_sched_fused(ccfg: CurveConfig, schedule: BitsSchedule, per_cand,
                      n_logged: int):
    """Build the jitted scheduled engine (all candidate depths, one jit).

    One training-step branch is compiled per candidate ``bits`` (the depth
    is static inside each branch — it fixes code dtypes and the contention
    scan length) and ``lax.switch`` picks the branch per round from the
    schedule's carried index.  The schedule's ``update`` consumes the
    round's protocol accounting (lane-mean collision fraction / rounds /
    correctness from the train-step metrics) and emits the next round's
    index — policy and training both stay on device.
    """
    steps, batch, n_train = ccfg.steps, ccfg.batch, ccfg.n_train
    cand_bits = jnp.asarray(schedule.candidates, jnp.int32)

    def make_branch(ci):
        vcfg_n, _vi, _opt, step_n, _si = per_cand[ci]
        proto_tmpl = vcfg_n.resolve_protocol()

        def branch(vals, opts, b, rngs, p):
            chan = (rngs, proto_tmpl.with_p_miss(p))
            return jax.vmap(step_n, in_axes=(0, 0, None, (0, 0)))(
                vals, opts, b, chan)
        return branch

    def make_eval_branch(ci, vviews, vlabels):
        vcfg_n = per_cand[ci][0]
        proto_tmpl = vcfg_n.resolve_protocol()

        def branch(vals, rngs, p):
            chan = (rngs, proto_tmpl.with_p_miss(p))
            return jax.vmap(
                lambda v, ch: vertical.loss_fn(vcfg_n, v, vviews, vlabels,
                                               rng=ch[0],
                                               protocol=ch[1])[1],
                in_axes=(0, (0, 0)))(vals, chan)
        return branch

    branches = [make_branch(ci) for ci in range(len(schedule.candidates))]

    def fused(params0, opt0, lane_keys, p, k_data, views, labels, vviews,
              vlabels, slots):
        obs.count(_TRACE + "sched")
        eval_branches = [make_eval_branch(ci, vviews, vlabels)
                         for ci in range(len(schedule.candidates))]
        lanes = lane_keys.shape[0]
        vals, opts = _lane_stack(params0, lanes), _lane_stack(opt0, lanes)
        hist = jnp.zeros((lanes, n_logged), jnp.float32)
        coll_hist = jnp.zeros((n_logged,), jnp.float32)
        st0 = schedule.init_state()
        idx0 = jnp.int32(schedule.init_index)

        def body(carry, x):
            vals, opts, hist, coll_hist, st, idx = carry
            step, slot = x
            bidx = _batch_indices(k_data, step, batch, n_train)
            b = (views[:, bidx], labels[bidx])
            rngs = _fold_lanes(lane_keys, step)
            vals, opts, met = jax.lax.switch(idx, branches, vals, opts, b,
                                             rngs, p)
            telemetry = {
                "collision_frac": jnp.mean(met["chan_collision_frac"]),
                "rounds": jnp.mean(met["chan_rounds"]),
                "correct_frac": jnp.mean(met["chan_correct_frac"]),
            }
            st, next_idx = schedule.update(st, telemetry)
            hist = hist.at[:, slot].set(met["loss_mean"], mode="drop")
            coll_hist = coll_hist.at[slot].set(
                telemetry["collision_frac"], mode="drop")
            return ((vals, opts, hist, coll_hist, st, next_idx),
                    (cand_bits[idx], idx))

        carry0 = (vals, opts, hist, coll_hist, st0, idx0)
        (vals, _opts, hist, coll_hist, _st, _idx), (bits_seq, idx_seq) = \
            jax.lax.scan(
                body, carry0, (jnp.arange(steps, dtype=jnp.int32), slots))

        # evaluate at the depth the final round actually trained with, so
        # the reported accuracy and bits_per_step[-1] name the same
        # operating point (the post-final-update index is never trained)
        rngs = _fold_lanes(lane_keys, steps)
        met = jax.lax.switch(idx_seq[-1], eval_branches, vals, rngs, p)
        return vals, hist, coll_hist, bits_seq, met["acc"], met["nll"]

    return jax.jit(fused)


def run_scheduled_curves(ccfg: CurveConfig, schedule: BitsSchedule
                         ) -> ScheduledCurveResult:
    """Train the ``p_miss`` lanes with a channel-aware ``BitsSchedule``.

    The backoff depth is re-chosen every round by ``schedule.update`` from
    the previous round's protocol accounting; all candidate depths compile
    into ONE jitted program (one ``lax.switch`` branch each) and the whole
    run — training scan, per-round policy, final channel-in-the-loop
    evaluation — is ONE host dispatch (``dispatch_counts()["sched"]``).

    The stochastic streams derive from
    ``_stream_keys(ccfg, candidates[init_index])``, so a schedule that
    never leaves its initial depth ``b`` (e.g. ``FixedBits(b)``) trains
    bit-for-bit the ``run_curves(bits=(b,))`` noisy lanes (property-tested
    in ``tests/test_protocol.py``).  Runs single-device (vmap lanes).
    """
    lanes = len(ccfg.p_miss)
    p_lanes = jnp.asarray(ccfg.lane_p_miss())

    views_j, labels_j, vv_j, vl_j = _make_data(ccfg)
    logged = ccfg.logged_steps()
    slots = jnp.asarray(_log_slots(ccfg, logged))

    per_cand = [_make_steps(ccfg, b) for b in schedule.candidates]
    init_bits = schedule.candidates[schedule.init_index]
    k_data, lane_keys = _stream_keys(ccfg, init_bits)

    # identical init for every candidate branch: the model is depth-
    # independent (bits only changes the fused forward), so one train state
    # serves the whole switch
    vcfg0, opt = per_cand[0][0], per_cand[0][2]
    params0 = vertical.init(vcfg0, jax.random.PRNGKey(ccfg.seed))
    opt0 = opt.init(params0)

    fused = _make_sched_fused(ccfg, schedule, per_cand, len(logged))
    obs.count(_DISPATCH + "sched")
    vals, hist, coll_hist, bits_seq, acc, nll = fused(
        params0, opt0, jnp.asarray(lane_keys), p_lanes, k_data, views_j,
        labels_j, vv_j, vl_j, slots)

    return ScheduledCurveResult(
        config=ccfg, schedule=schedule, p_miss=ccfg.lane_p_miss(),
        acc=np.asarray(acc, np.float64)[:lanes],
        nll=np.asarray(nll, np.float64)[:lanes],
        loss_history=np.asarray(hist, np.float64)[:lanes].T,
        collision_frac=np.asarray(coll_hist, np.float64),
        bits_per_step=np.asarray(bits_seq, np.int64),
        logged_steps=np.asarray(logged), params=vals)


# ---------------------------------------------------------------------------
# the 2-D engine: p_miss lanes x data-parallel shards, compressed all-reduce
# ---------------------------------------------------------------------------

def _make_fused_dp(ccfg: CurveConfig, compress: CompressedAllReduce,
                   per_bits, n_logged: int, n_s: int, n_d: int):
    """Build the jitted 2-D engine for one ``bits`` value.

    Every training step, each DP rank draws its slice of the shared batch
    stream, runs the channel-in-the-loop forward on its own sensing key
    (``fold_in(lane_step_key, rank)``), and the sparse gradients all-reduce
    over the ``"d"`` axis via ``compress.reduce`` — all inside the single
    ``lax.scan``/dispatch of the fused-engine contract.  Per-step measured
    payload bits ride the scan carry next to the loss history.

    The ``"d"`` axis is either a mesh axis (``n_d == dp_shards``, gradients
    cross devices) or a ``vmap(axis_name="d")`` axis on one device —
    ``compress.reduce``'s gather+fixed-order-sum makes the two bit-for-bit
    identical (``dp_mesh_shape`` never splits the DP axis between the two).
    Lanes shard over ``"s"`` exactly as in :func:`_make_fused`.
    """
    vcfg_n = per_bits[0]
    opt = per_bits[2]
    proto_tmpl = vcfg_n.resolve_protocol()
    steps, batch, n_train = ccfg.steps, ccfg.batch, ccfg.n_train
    dp_shards = ccfg.dp_shards
    shard_b = batch // dp_shards
    mesh_dp = n_d > 1

    grad_fn = jax.value_and_grad(
        lambda v, bv, bl, rng, p_l: vertical.loss_fn(
            vcfg_n, v, bv, bl, rng=rng,
            protocol=proto_tmpl.with_p_miss(p_l)),
        has_aux=True)

    def dp_lanes(params0, opt0, err0, lane_keys, p, shard_ids, k_data,
                 views, labels, vviews, vlabels, slots):
        lanes = lane_keys.shape[0]          # shard-local lane count
        d_local = shard_ids.shape[0]        # 1 on the mesh path, D vmapped
        vals = _lane_stack(_lane_stack(params0, d_local), lanes)
        opts = _lane_stack(_lane_stack(opt0, d_local), lanes)
        hist = jnp.zeros((lanes, n_logged), jnp.float32)
        pay_hist = jnp.zeros((lanes, n_logged), jnp.int32)
        pay_total = jnp.zeros((lanes,), jnp.int32)

        def rank_step(vals, opts, err, shard_id, rng_lane, p_l, idx):
            """One DP rank of one lane: local grads -> compressed all-reduce
            over "d" -> rank-mean update.  Params/opt stay bitwise identical
            across ranks (same reduced gradient); only ``err`` diverges."""
            rng = jax.random.fold_in(rng_lane, shard_id)
            idx_s = jax.lax.dynamic_slice(idx, (shard_id * shard_b,),
                                          (shard_b,))
            (loss, _met), grads = grad_fn(vals, views[:, idx_s],
                                          labels[idx_s], rng, p_l)
            reduced, err, acct = compress.reduce(grads, err, axis_name="d")
            n_ranks = jax.lax.psum(jnp.int32(1), "d")
            reduced = jax.tree.map(lambda g: g / n_ranks, reduced)
            vals, opts, _stats = opt.update(reduced, opts, vals)
            loss_mean = jnp.mean(jax.lax.all_gather(loss, "d"))
            return vals, opts, err, loss_mean, acct.payload_bits

        if mesh_dp:
            # the mesh carries "d": each device holds one rank (d_local==1);
            # only the lane axis is vmapped — collectives hit the mesh axis
            def step_all(vals, opts, errs, rngs, idx):
                v, o, e = (jax.tree.map(lambda x: x[:, 0], t)
                           for t in (vals, opts, errs))
                v, o, e, lm, pay = jax.vmap(
                    rank_step, in_axes=(0, 0, 0, None, 0, 0, None))(
                        v, o, e, shard_ids[0], rngs, p, idx)
                v, o, e = (jax.tree.map(lambda x: x[:, None], t)
                           for t in (v, o, e))
                return v, o, e, lm, pay
        else:
            # single-device DP: the "d" axis is a named vmap axis — the
            # collectives see the identical (D, ...) stacking order
            ranks = jax.vmap(rank_step, in_axes=(0, 0, 0, 0, None, None,
                                                 None), axis_name="d")

            def step_all(vals, opts, errs, rngs, idx):
                v, o, e, lm, pay = jax.vmap(
                    ranks, in_axes=(0, 0, 0, None, 0, 0, None))(
                        vals, opts, errs, shard_ids, rngs, p, idx)
                # per-rank outputs are rank-invariant (post-psum): take rank 0
                return v, o, e, lm[:, 0], pay[:, 0]

        def body(carry, x):
            vals, opts, errs, hist, pay_hist, pay_total = carry
            step, slot = x
            idx = _batch_indices(k_data, step, batch, n_train)
            rngs = _fold_lanes(lane_keys, step)
            vals, opts, errs, lm, pay = step_all(vals, opts, errs, rngs, idx)
            hist = hist.at[:, slot].set(lm, mode="drop")
            pay_hist = pay_hist.at[:, slot].set(pay, mode="drop")
            pay_total = pay_total + pay
            return (vals, opts, errs, hist, pay_hist, pay_total), None

        carry0 = (vals, opts, err0, hist, pay_hist, pay_total)
        (vals, _opts, _errs, hist, pay_hist, pay_total), _ = jax.lax.scan(
            body, carry0, (jnp.arange(steps, dtype=jnp.int32), slots))

        # rank replicas are bitwise identical: evaluate the local rank's copy
        vals_l = jax.tree.map(lambda x: x[:, 0], vals)
        eval_rngs = _fold_lanes(lane_keys, steps)
        met = jax.vmap(
            lambda v, r, p_l: vertical.loss_fn(
                vcfg_n, v, vviews, vlabels, rng=r,
                protocol=proto_tmpl.with_p_miss(p_l))[1],
            in_axes=(0, 0, 0))(vals_l, eval_rngs, p)
        return vals_l, hist, pay_hist, pay_total, met["acc"], met["nll"]

    dp_engine = dp_lanes
    if n_d > 1:
        dp_engine = sim_shard.shard_2d(
            dp_lanes, n_s, n_d,
            in_specs=(P(), P(), P("s", "d"), P("s"), P("s"), P("d"), P(),
                      P(), P(), P(), P(), P()),
            out_specs=(P("s"),) * 6)
    elif n_s > 1:
        dp_engine = sim_shard.shard_1d(
            dp_lanes, n_s,
            in_specs=(P(), P(), P("s"), P("s"), P("s"), P(), P(), P(), P(),
                      P(), P(), P()),
            out_specs=(P("s"),) * 6)

    def fused(params0, opt0, err0, lane_keys, p, shard_ids, k_data, views,
              labels, vviews, vlabels, slots):
        obs.count(_TRACE + "fused_dp")
        return dp_engine(params0, opt0, err0, lane_keys, p, shard_ids,
                         k_data, views, labels, vviews, vlabels, slots)

    return jax.jit(fused)


def _run_curves_dp(ccfg: CurveConfig, compress: CompressedAllReduce,
                   n_devices) -> DPCurveResult:
    lanes = len(ccfg.p_miss)
    p_lanes = ccfg.lane_p_miss()
    n_s, n_d = sim_shard.dp_mesh_shape(n_devices, lanes, ccfg.dp_shards)
    p_pad = jnp.asarray(sim_shard.pad_lanes(p_lanes, n_s))
    l_pad = p_pad.shape[0]
    shard_ids = jnp.arange(ccfg.dp_shards, dtype=jnp.int32)

    views_j, labels_j, vv_j, vl_j = _make_data(ccfg)
    logged = ccfg.logged_steps()
    slots = jnp.asarray(_log_slots(ccfg, logged))

    acc = np.zeros((len(ccfg.bits), lanes), np.float64)
    nll = np.zeros_like(acc)
    hist = np.zeros((len(ccfg.bits), len(logged), lanes), np.float64)
    pay = np.zeros((len(ccfg.bits), len(logged), lanes), np.int64)
    pay_total = np.zeros((len(ccfg.bits), lanes), np.int64)
    params_out = []
    pay_step = dense_step = 0

    for bi, bits in enumerate(ccfg.bits):
        per_bits = _make_steps(ccfg, bits)
        vcfg_n, opt = per_bits[0], per_bits[2]
        k_data, lane_keys = _stream_keys(ccfg, bits)
        keys_pad = jnp.asarray(
            sim_shard.pad_lanes(np.asarray(lane_keys), n_s))

        params0 = vertical.init(vcfg_n, jax.random.PRNGKey(ccfg.seed))
        opt0 = opt.init(params0)
        # per-(lane, rank) error-feedback memory, a traced scan carry
        err0 = jax.tree.map(
            lambda x: jnp.zeros((l_pad, ccfg.dp_shards) + x.shape,
                                jnp.float32), params0)
        # the analytic per-step bill every measured step must equal
        pay_step = compress.payload_bits(params0) * ccfg.dp_shards
        dense_step = compress.dense_bits(params0) * ccfg.dp_shards

        fused = _make_fused_dp(ccfg, compress, per_bits, len(logged), n_s,
                               n_d)
        obs.count(_DISPATCH + "fused_dp")
        vals, hist_b, pay_b, pay_tot_b, acc_b, nll_b = fused(
            params0, opt0, err0, keys_pad, p_pad, shard_ids, k_data,
            views_j, labels_j, vv_j, vl_j, slots)

        acc[bi] = np.asarray(acc_b)[:lanes]
        nll[bi] = np.asarray(nll_b)[:lanes]
        hist[bi] = np.asarray(hist_b)[:lanes].T
        pay[bi] = np.asarray(pay_b, np.int64)[:lanes].T
        pay_total[bi] = np.asarray(pay_tot_b, np.int64)[:lanes]
        params_out.append(jax.tree.map(lambda x: x[:lanes], vals))

    return DPCurveResult(
        config=ccfg, compress=compress, p_miss=ccfg.lane_p_miss(),
        acc=acc, nll=nll, loss_history=hist,
        dp_payload_bits=pay, dp_payload_bits_total=pay_total,
        dp_payload_bits_step=int(pay_step),
        dp_dense_bits_step=int(dense_step),
        logged_steps=np.asarray(logged), params=params_out)


def run_curves_dp(ccfg: CurveConfig, compress: CompressedAllReduce, *,
                  n_devices: Optional[int] = None) -> DPCurveResult:
    """Train the 2-D (p_miss lanes x DP shards) grid with compressed comms.

    Each lane's training step splits the shared batch stream across
    ``ccfg.dp_shards`` data-parallel ranks; every rank sparsifies its
    gradients (top-k + error feedback, per-rank EF memory) and the sparse
    trees all-reduce via ``compress.reduce`` *inside* the fused scan — the
    whole run stays ONE host dispatch per ``bits`` value
    (``dispatch_counts()["fused_dp"]``), with the measured DP payload bits
    accumulated on device alongside the loss history.

    Placement follows :func:`repro.sim.shard.dp_mesh_shape`: the DP axis
    lands entirely on the device mesh (when ``dp_shards`` divides into the
    available devices) or entirely on a named vmap axis, never split —
    results are bit-for-bit identical across ``n_devices`` (the
    forced-multi-device subprocess test in ``tests/test_dp_curves.py``).

    Feed the result to ``repro.sim.results.summarize_dp_curves`` for the
    unified uplink + DP all-reduce communication report.
    """
    return _run_curves_dp(ccfg, compress, n_devices)

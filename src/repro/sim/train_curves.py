"""Channel-in-the-loop training curves: accuracy vs channel quality.

The paper's end-to-end experiment: the vertical learner fuses worker
embeddings through the *simulated* OCS channel (``Protocol.ocs``), and
short training runs sweep the ``p_miss x bits`` grid into accuracy tables
(``repro.sim.results``).

One engine runs every curve.  Its skeleton (:func:`_lanes`, :func:`_engine`)
builds ONE jitted dispatch per ``bits`` value: a ``lax.scan`` over the
steps that draws each batch on device, folds the step into every lane's
sensing key, keeps the logged series in ``(lanes, n_logged)`` histories and
sums the counted ones, then evaluates channel-in-the-loop under fresh keys.
On several devices the lane (and DP rank) axis runs under ``shard_map``,
its specs derived from the arguments that carry it.  Four lane programs
(:class:`_Program`) fill the skeleton in:

* plain (:func:`run_curves`): lanes vmapped over the traced ``(rng,
  Protocol)`` channel state, beside an ideal ``Protocol.ideal_max(bits)``
  lane (the same program, one lane, no keys);
* fault (:func:`run_fault_curves`): ``FaultModel`` lanes whose chains ride
  the scan carry;
* scheduled (:func:`run_scheduled_curves`): a ``BitsSchedule`` switches
  the depth per round from the last round's accounting;
* DP (:func:`run_curves_dp`): lanes x data-parallel ranks whose top-k
  gradients all-reduce over ``"d"`` in the scan, payload bits measured.

One host runner (:func:`_dispatches`, :func:`_run`) makes the data once, and
per dispatch the keys, the shared init and the padded lane arguments.
:func:`trace_counts` and :func:`dispatch_counts` count compilations and
dispatches (one per ``bits`` value; ``sched``: one per run).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

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
    """Times each curve engine has been traced: one per ``bits`` value (one
    ``sched`` trace per run), however many lanes the grid has."""
    return obs.view(_TRACE, _COUNTER_KEYS)


def reset_dispatch_counts() -> None:
    """Zero the per-engine host-dispatch counters."""
    obs.reset(_DISPATCH)


def dispatch_counts() -> Dict[str, int]:
    """Jitted-engine dispatches issued from the host by each curve runner:
    ONE per ``bits`` value (``sched``: one per run).
    ``benchmarks/bench_curves.py`` asserts the ``<= ceil(steps/log_every)
    + 2`` per-bits bound against a fall-back to per-step driving."""
    return obs.view(_DISPATCH, _COUNTER_KEYS)


# ---------------------------------------------------------------------------
# configuration + result containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CurveConfig:
    """One accuracy-vs-channel-quality experiment grid.

    ``p_miss`` lanes are scalars or length-``n_workers`` sequences (near/far
    users, e.g. ``repro.sim.scenarios.near_far_p_miss``); scalars broadcast.
    ``backend`` is every lane protocol's contention engine (``"scan"`` or
    the fused ``"pallas"`` kernel, bit-for-bit interchangeable).
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
    dp_shards: int = 1                   # DP ranks of run_curves_dp

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

    Lane axis L == ``len(config.p_miss)``, bits axis in ``config.bits``
    order.  ``*_ideal`` rows come from the ideal ``Protocol.ideal_max(bits)``
    lane.  ``p_miss`` is the float32 lane array the engine traces, so the
    reported operating points are exactly the compiled ones.
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

    ``bits_per_step`` records the depth every step trained with (the first
    is ``schedule.candidates[schedule.init_index]``); ``collision_frac`` is
    the lane-mean collision fraction at the logged steps, the telemetry the
    policy consumed.
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

    The lane axis L indexes ``fault_lanes``.  ``stale_age`` is the
    staleness (frames since the last resolved frame) at the logged steps;
    the ``*_frames``/``retry_slots`` arrays are whole-run totals billed by
    ``FaultAccounting``.
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

    The DP payload bits are MEASURED inside the fused scan from the kept
    elements of every rank's exact-k masks (``DPAccounting``).
    ``dp_payload_bits_step`` / ``dp_dense_bits_step`` are the analytic
    per-step totals (all ranks) the measurement must equal.
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
# shared pieces: data/key streams, the optimizer, per-bits train steps
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


def _stream_keys(ccfg: CurveConfig, bits: int, lanes: int):
    """Root keys of the batch and sensing streams of one ``bits`` cell.

    Every stochastic input derives from these: ``_batch_indices(k_data,
    step)`` for the shared batch stream, ``fold_in(lane_keys[l], step)``
    for lane ``l``'s sensing key (``step == steps`` evaluates), alike for
    every program.
    """
    base = jax.random.PRNGKey(ccfg.seed + 7919 * bits)
    k_data, k_noise = jax.random.split(base)
    return k_data, jax.random.split(k_noise, lanes)


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

    The noisy loss takes the channel state ``chan = (rng, protocol)``, the
    lane's sensing key and ``Protocol`` pytree (its ``p_miss`` leaf the only
    traced difference between lanes).  Every program trains with ``opt``.
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
# the engine skeleton: one lax.scan per lane program, one dispatch
# ---------------------------------------------------------------------------

def _stack_state(params0, opt0, lane, n):
    return _lane_stack(params0, n), _lane_stack(opt0, n)


def _trained(state, emits):
    return state[0], None


@dataclasses.dataclass(frozen=True)
class _Program:
    """What one kind of lane carries and does inside the fused scan.

    ``args``: its dispatch arguments between ``opt0`` and ``k_data``, with
    the mesh axes they carry (``"s"`` lanes, ``"d"`` DP ranks).
    ``step(state, batch, rngs, lane)`` -> ``(state, metrics, emit)``:
    ``batch`` is the step's rows (``(views, labels, indices)`` with
    ``rows`` off), ``rngs`` the lanes' step keys, ``emit`` a per-step
    output or None.  ``evaluate(vals, ctx, rngs, lane, vviews, vlabels)``
    -> metrics.  ``init(params0, opt0, lane, n)`` -> the carry of ``n``
    lanes; ``finish(state, emits)`` -> the lanes to return and what their
    evaluation needs.  ``logs``: ``(metric, dtype, per_lane)`` series kept
    at the logged steps; ``sums``: int32 per-lane metrics summed over the
    run.  Returns ``(vals, *logs, *sums[, emits], acc, nll)``.
    """

    args: Tuple[Tuple[str, str], ...]
    step: Callable
    evaluate: Callable
    init: Callable = _stack_state
    finish: Callable = _trained
    logs: Tuple[Tuple[str, object, bool], ...] = (
        ("loss_mean", jnp.float32, True),)
    sums: Tuple[str, ...] = ()
    rows: bool = True


def _lanes(ccfg: CurveConfig, prog: _Program, n_logged: int, n_s: int = 1,
           n_d: int = 1):
    """One program's whole run as a function of ``(params0, opt0, *args,
    k_data, views, labels, vviews, vlabels, slots)``, under ``shard_map``
    if ``n_s * n_d > 1`` (every output of a sharded program leads with its
    lanes)."""
    steps, batch, n_train = ccfg.steps, ccfg.batch, ccfg.n_train
    names = [name for name, _ in prog.args]

    def run(params0, opt0, *args):
        lane = dict(zip(names, args))
        k_data, views, labels, vviews, vlabels, slots = args[len(names):]
        keys = lane.get("lane_keys")
        n = 1 if keys is None else keys.shape[0]    # shard-local lanes
        state = prog.init(params0, opt0, lane, n)
        logs = tuple(jnp.zeros((n, n_logged) if per_lane else (n_logged,),
                               dtype) for _, dtype, per_lane in prog.logs)
        sums = tuple(jnp.zeros((n,), jnp.int32) for _ in prog.sums)

        def body(carry, x):
            state, logs, sums = carry
            step, slot = x
            idx = _batch_indices(k_data, step, batch, n_train)
            b = (views[:, idx], labels[idx]) if prog.rows else (views,
                                                                 labels, idx)
            rngs = None if keys is None else _fold_lanes(keys, step)
            state, met, emit = prog.step(state, b, rngs, lane)
            logs = tuple(
                h.at[(slice(None),) * (h.ndim - 1) + (slot,)].set(
                    met[name], mode="drop")
                for h, (name, _, _) in zip(logs, prog.logs))
            sums = tuple(t + met[name] for t, name in zip(sums, prog.sums))
            return (state, logs, sums), emit

        (state, logs, sums), emits = jax.lax.scan(
            body, (state, logs, sums),
            (jnp.arange(steps, dtype=jnp.int32), slots))
        vals, ctx = prog.finish(state, emits)
        rngs = None if keys is None else _fold_lanes(keys, steps)
        met = prog.evaluate(vals, ctx, rngs, lane, vviews, vlabels)
        tail = () if emits is None else (emits,)
        return (vals, *logs, *sums, *tail, met["acc"], met["nll"])

    if n_s * n_d == 1:
        return run
    mesh = "sd" if n_d > 1 else "s"
    in_specs = ((P(), P())
                + tuple(P(*(a for a in axes if a in mesh))
                        for _, axes in prog.args) + (P(),) * 6)
    out_specs = (P("s"),) * (3 + len(prog.logs) + len(prog.sums))
    if n_d > 1:
        return sim_shard.shard_2d(run, n_s, n_d, in_specs, out_specs)
    return sim_shard.shard_1d(run, n_s, in_specs, out_specs)


def _engine(ccfg: CurveConfig, name: str, progs: Sequence[_Program],
            n_logged: int, n_s: int = 1, n_d: int = 1):
    """The jitted dispatch of one ``bits`` value (a ``name`` trace): each
    program takes a prefix of the first one's lane arguments, and one with
    none (the ideal lane) runs unsharded."""
    runs = [_lanes(ccfg, p, n_logged, n_s, n_d) if p.args
            else _lanes(ccfg, p, n_logged) for p in progs]
    n_args = len(progs[0].args)

    def fused(params0, opt0, *args):
        obs.count(_TRACE + name)
        outs = tuple(run(params0, opt0, *args[:len(p.args)], *args[n_args:])
                     for run, p in zip(runs, progs))
        return outs if len(outs) > 1 else outs[0]

    return jax.jit(fused)


# ---------------------------------------------------------------------------
# the lane programs
# ---------------------------------------------------------------------------

def _plain(per_bits, noisy: bool = True) -> _Program:
    """Noisy lanes over the traced ``(rng, Protocol)`` channel state; with
    ``noisy=False`` the ideal lane (``step_i``, one lane, no keys)."""
    vcfg_n, vcfg_i, _opt, step_n, step_i = per_bits

    if not noisy:
        def step_ideal(state, b, rngs, lane):
            vals, opts, met = jax.vmap(step_i, in_axes=(0, 0, None))(
                *state, b)
            return (vals, opts), met, None

        def eval_ideal(vals, ctx, rngs, lane, vviews, vlabels):
            return jax.vmap(
                lambda v: vertical.loss_fn(vcfg_i, v, vviews, vlabels)[1])(
                    vals)
        return _Program((), step_ideal, eval_ideal)

    proto = vcfg_n.resolve_protocol()

    def step(state, b, rngs, lane):
        chan = (rngs, proto.with_p_miss(lane["p"]))
        vals, opts, met = jax.vmap(step_n, in_axes=(0, 0, None, (0, 0)))(
            *state, b, chan)
        return (vals, opts), met, None

    def evaluate(vals, ctx, rngs, lane, vviews, vlabels):
        chan = (rngs, proto.with_p_miss(lane["p"]))
        return jax.vmap(
            lambda v, ch: vertical.loss_fn(vcfg_n, v, vviews, vlabels,
                                           rng=ch[0], protocol=ch[1])[1],
            in_axes=(0, (0, 0)))(vals, chan)

    return _Program((("lane_keys", "s"), ("p", "s")), step, evaluate)


def _fault(ccfg: CurveConfig, per_bits) -> _Program:
    """``FaultModel`` lanes, channel state ``(rng, protocol, fault,
    fault_state)``; evaluated under the final chain state with a fresh
    stale cache of the eval batch's shape."""
    from repro import faults

    vcfg_n, _vi, opt, _sn, _si = per_bits
    proto = vcfg_n.resolve_protocol()

    def fault_loss(values, batch, chan):
        rng, prot, fm, fs = chan
        return vertical.loss_fn(vcfg_n, values, *batch, rng=rng,
                                protocol=prot, fault=fm, fault_state=fs)

    step_f = make_train_step(fault_loss, opt, with_rng=True)

    def init(params0, opt0, lane, n):
        return _stack_state(params0, opt0, lane, n) + (lane["fs0"],)

    def step(state, b, rngs, lane):
        vals, opts, fs = state
        vals, opts, met = jax.vmap(
            step_f, in_axes=(0, 0, None, (0, None, 0, 0)))(
                vals, opts, b, (rngs, proto, lane["fm"], fs))
        met = dict(met)
        fs = met.pop("fault_state")
        return (vals, opts, fs), met, None

    def finish(state, emits):
        vals, _opts, fs = state
        lanes = fs.age.shape[0]
        return vals, faults.FaultState(
            bad=fs.bad, offline=fs.offline,
            stale=jnp.zeros((lanes, ccfg.n_val, ccfg.embed_dim),
                            jnp.float32),
            age=jnp.zeros((lanes,), jnp.int32),
            consec=jnp.zeros((lanes,), jnp.int32))

    def evaluate(vals, eval_fs, rngs, lane, vviews, vlabels):
        return jax.vmap(
            lambda v, r, fm_l, fs_l: vertical.loss_fn(
                vcfg_n, v, vviews, vlabels, rng=r, protocol=proto,
                fault=fm_l, fault_state=fs_l)[1])(
                    vals, rngs, lane["fm"], eval_fs)

    return _Program(
        (("lane_keys", "s"), ("fm", "s"), ("fs0", "s")), step, evaluate,
        init=init, finish=finish,
        logs=(("loss_mean", jnp.float32, True),
              ("fault_stale_age", jnp.int32, True)),
        sums=("fault_dropped_frames", "fault_outage", "fault_retry_slots"))


def _sched(schedule: BitsSchedule, per_cand) -> _Program:
    """The plain program of each candidate depth as a ``lax.switch``
    branch, picked by the index ``schedule.update`` carries; evaluates at
    the depth the last step trained with."""
    plain = [_plain(per) for per in per_cand]

    def init(params0, opt0, lane, n):
        return _stack_state(params0, opt0, lane, n) + (
            schedule.init_state(), jnp.int32(schedule.init_index))

    def step(state, b, rngs, lane):
        vals, opts, st, idx = state
        (vals, opts), met, _ = jax.lax.switch(
            idx, [p.step for p in plain], (vals, opts), b, rngs,
            {"p": lane["p"]})
        telemetry = {
            "collision_frac": jnp.mean(met["chan_collision_frac"]),
            "rounds": jnp.mean(met["chan_rounds"]),
            "correct_frac": jnp.mean(met["chan_correct_frac"]),
        }
        st, next_idx = schedule.update(st, telemetry)
        return ((vals, opts, st, next_idx),
                {"loss_mean": met["loss_mean"], **telemetry}, idx)

    def finish(state, idx_seq):
        return state[0], idx_seq[-1]

    def evaluate(vals, idx, rngs, lane, vviews, vlabels):
        branches = [lambda v, r, p, ev=prog.evaluate: ev(
            v, None, r, {"p": p}, vviews, vlabels) for prog in plain]
        return jax.lax.switch(idx, branches, vals, rngs, lane["p"])

    return _Program(
        (("lane_keys", "s"), ("p", "s")), step, evaluate, init=init,
        finish=finish, logs=(("loss_mean", jnp.float32, True),
                             ("collision_frac", jnp.float32, False)))


def _dp(ccfg: CurveConfig, compress: CompressedAllReduce, per_bits,
        mesh_dp: bool) -> _Program:
    """p_miss lanes x DP ranks: each rank trains its slice of the batch on
    its own key (``fold_in(lane_step_key, rank)``).  ``"d"`` is a mesh axis
    (``mesh_dp``) or a named vmap axis, with the reduce's gather and
    fixed-order sum the same on both."""
    vcfg_n, _vi, opt, _sn, _si = per_bits
    proto = vcfg_n.resolve_protocol()
    shard_b = ccfg.batch // ccfg.dp_shards
    grad_fn = jax.value_and_grad(
        lambda v, bv, bl, rng, p_l: vertical.loss_fn(
            vcfg_n, v, bv, bl, rng=rng, protocol=proto.with_p_miss(p_l)),
        has_aux=True)

    def init(params0, opt0, lane, n):
        d_local = lane["shard_ids"].shape[0]   # 1 on the mesh, D vmapped
        return (_lane_stack(_lane_stack(params0, d_local), n),
                _lane_stack(_lane_stack(opt0, d_local), n), lane["err0"])

    def step(state, b, rngs, lane):
        views, labels, idx = b
        p, shard_ids = lane["p"], lane["shard_ids"]

        def rank_step(vals, opts, err, shard_id, rng_lane, p_l, idx):
            # params/opt stay bitwise alike across ranks; err diverges
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
            # the mesh carries "d" (one rank a device): vmap the lanes only
            v, o, e = (jax.tree.map(lambda x: x[:, 0], t) for t in state)
            v, o, e, lm, pay = jax.vmap(
                rank_step, in_axes=(0, 0, 0, None, 0, 0, None))(
                    v, o, e, shard_ids[0], rngs, p, idx)
            state = tuple(jax.tree.map(lambda x: x[:, None], t)
                          for t in (v, o, e))
        else:
            # one device: "d" is a named vmap axis, the same stacking order
            ranks = jax.vmap(rank_step, in_axes=(0, 0, 0, 0, None, None,
                                                 None), axis_name="d")
            *state, lm, pay = jax.vmap(
                ranks, in_axes=(0, 0, 0, None, 0, 0, None))(
                    *state, shard_ids, rngs, p, idx)
            # per-rank outputs are rank-invariant (post-psum): take rank 0
            state, lm, pay = tuple(state), lm[:, 0], pay[:, 0]
        return state, {"loss_mean": lm, "payload_bits": pay}, None

    def finish(state, emits):
        # rank replicas are bitwise identical: return the local rank's copy
        return jax.tree.map(lambda x: x[:, 0], state[0]), None

    def evaluate(vals, ctx, rngs, lane, vviews, vlabels):
        return jax.vmap(
            lambda v, r, p_l: vertical.loss_fn(
                vcfg_n, v, vviews, vlabels, rng=r,
                protocol=proto.with_p_miss(p_l))[1],
            in_axes=(0, 0, 0))(vals, rngs, lane["p"])

    return _Program(
        (("err0", "sd"), ("lane_keys", "s"), ("p", "s"), ("shard_ids", "d")),
        step, evaluate, init=init, finish=finish,
        logs=(("loss_mean", jnp.float32, True),
              ("payload_bits", jnp.int32, True)),
        sums=("payload_bits",), rows=False)


def _make_fused(ccfg: CurveConfig, per_bits, n_logged: int, n_dev: int):
    """The plain engine's dispatch for one ``bits`` value, trained with the
    steps of ``per_bits`` (that value's ``_make_steps`` tuple).  Arguments
    ``(params0, opt0, lane_keys, p, k_data, views, labels, vviews, vlabels,
    slots)``; outputs ``((vals, hist, acc, nll), (vals_i, hist_i, acc_i,
    nll_i))``.  With ``n_dev > 1`` the noisy lanes shard over a 1-D mesh."""
    return _engine(ccfg, "fused", (_plain(per_bits), _plain(per_bits, False)),
                   n_logged, n_s=n_dev)


# ---------------------------------------------------------------------------
# the host runner and the public runners
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Job:
    """One runner's dispatches: ``build(per_bits_list, n_logged)`` and
    ``lane_args(lane_keys, params0)`` (in the program's order) for each of
    ``groups``' ``(key_bits, bits)`` (default: one per ``ccfg.bits``)."""

    name: str
    lanes: int
    build: Callable
    lane_args: Callable
    n_pad: int = 1
    groups: Optional[Sequence[Tuple[int, Tuple[int, ...]]]] = None


def _dispatches(ccfg: CurveConfig, job: _Job):
    """Yield ``(fused, params0, opt0, lane, shared)`` per dispatch, the
    data made once; every lane shares the init and the batch stream."""
    views, labels, vviews, vlabels = _make_data(ccfg)
    logged = ccfg.logged_steps()
    slots = jnp.asarray(_log_slots(ccfg, logged))
    for key_bits, bits in job.groups or [(b, (b,)) for b in ccfg.bits]:
        per = [_make_steps(ccfg, b) for b in bits]
        k_data, lane_keys = _stream_keys(ccfg, key_bits, job.lanes)
        keys = jnp.asarray(sim_shard.pad_lanes(np.asarray(lane_keys),
                                               job.n_pad))
        params0 = vertical.init(per[0][0], jax.random.PRNGKey(ccfg.seed))
        opt0 = per[0][2].init(params0)
        yield (job.build(per, len(logged)), params0, opt0,
               job.lane_args(keys, params0),
               (k_data, views, labels, vviews, vlabels, slots))


def _run(ccfg: CurveConfig, job: _Job) -> List:
    """Every dispatch of ``job``, padding dropped, outputs on the device."""
    outs = []
    for fused, params0, opt0, lane, shared in _dispatches(ccfg, job):
        obs.count(_DISPATCH + job.name)
        out = fused(params0, opt0, *lane.values(), *shared)
        if job.n_pad > 1:
            out = jax.tree.map(lambda x: x[:job.lanes], out)
        outs.append(out)
    return outs


def _fetch(outs, i: int, dtype=np.float64, lanes_last: bool = False):
    """Output ``i`` of every dispatch on the host, stacked over them."""
    return np.stack([np.asarray(o[i], dtype).T if lanes_last
                     else np.asarray(o[i], dtype) for o in outs])


def _p_lanes(ccfg: CurveConfig, n_pad: int):
    return jnp.asarray(sim_shard.pad_lanes(ccfg.lane_p_miss(), n_pad))


def _plain_job(ccfg: CurveConfig, n_devices) -> _Job:
    n_dev = sim_shard.lane_devices(n_devices, len(ccfg.p_miss))
    p = _p_lanes(ccfg, n_dev)
    return _Job("fused", len(ccfg.p_miss),
                lambda per, n_logged: _make_fused(ccfg, per[0], n_logged,
                                                  n_dev),
                lambda keys, params0: {"lane_keys": keys, "p": p}, n_dev)


def _fault_job(ccfg: CurveConfig, fault_lanes: Sequence) -> _Job:
    from repro import faults

    lanes = len(fault_lanes)
    if lanes == 0:
        raise ValueError("fault_lanes needs at least one FaultModel")
    policies = {fm.policy for fm in fault_lanes}
    if len(policies) != 1:
        raise ValueError(
            f"all fault lanes must share one DegradePolicy (static "
            f"metadata — one compile per policy), got {policies}")
    fm = jax.tree.map(lambda *xs: jnp.stack(xs), *fault_lanes)
    fs0 = _lane_stack(faults.init_state(ccfg.n_workers,
                                        (ccfg.batch, ccfg.embed_dim)), lanes)
    return _Job("fused_faults", lanes,
                lambda per, n_logged: _engine(
                    ccfg, "fused_faults", (_fault(ccfg, per[0]),), n_logged),
                lambda keys, params0: {"lane_keys": keys, "fm": fm,
                                       "fs0": fs0})


def _sched_job(ccfg: CurveConfig, schedule: BitsSchedule) -> _Job:
    # one init serves every candidate branch: the model is depth-
    # independent (bits only changes the fused forward)
    cands = tuple(schedule.candidates)
    p = _p_lanes(ccfg, 1)
    return _Job("sched", len(ccfg.p_miss),
                lambda per, n_logged: _engine(
                    ccfg, "sched", (_sched(schedule, per),), n_logged),
                lambda keys, params0: {"lane_keys": keys, "p": p},
                groups=[(cands[schedule.init_index], cands)])


def _dp_job(ccfg: CurveConfig, compress: CompressedAllReduce,
            n_devices) -> _Job:
    n_s, n_d = sim_shard.dp_mesh_shape(n_devices, len(ccfg.p_miss),
                                       ccfg.dp_shards)
    p = _p_lanes(ccfg, n_s)
    shard_ids = jnp.arange(ccfg.dp_shards, dtype=jnp.int32)

    def lane_args(keys, params0):
        # per-(lane, rank) error-feedback memory, a traced scan carry
        err0 = jax.tree.map(
            lambda x: jnp.zeros((keys.shape[0], ccfg.dp_shards) + x.shape,
                                jnp.float32), params0)
        return {"err0": err0, "lane_keys": keys, "p": p,
                "shard_ids": shard_ids}

    return _Job("fused_dp", len(ccfg.p_miss),
                lambda per, n_logged: _engine(
                    ccfg, "fused_dp", (_dp(ccfg, compress, per[0], n_d > 1),),
                    n_logged, n_s, n_d),
                lane_args, n_s)


def run_curves(ccfg: Optional[CurveConfig] = None, *,
               n_devices: Optional[int] = None) -> CurveResult:
    """Train the p_miss lane axis through the simulated channel, per bits.

    ``ccfg=None`` runs the default :class:`CurveConfig` grid.  ONE dispatch
    per ``bits`` value trains every lane and the ideal lane from one init
    on one batch stream.  ``n_devices`` shards the lanes over local
    devices (``None``: all of them; ``1``: the single-device vmap path).
    """
    ccfg = ccfg if ccfg is not None else CurveConfig()
    noisy, ideal = zip(*_run(ccfg, _plain_job(ccfg, n_devices)))
    return CurveResult(
        config=ccfg, p_miss=ccfg.lane_p_miss(),
        acc=_fetch(noisy, 2), nll=_fetch(noisy, 3),
        acc_ideal=_fetch(ideal, 2)[:, 0], nll_ideal=_fetch(ideal, 3)[:, 0],
        loss_history=_fetch(noisy, 1, lanes_last=True),
        ideal_loss_history=_fetch(ideal, 1)[:, 0],
        logged_steps=np.asarray(ccfg.logged_steps()),
        noisy_params=[o[0] for o in noisy],
        ideal_params=[o[0] for o in ideal])


def run_fault_curves(ccfg: CurveConfig, fault_lanes: Sequence
                     ) -> FaultCurveResult:
    """Train a grid of channel-fault lanes through the fused engine.

    ``fault_lanes``: ``repro.faults.FaultModel`` values sharing one
    ``DegradePolicy`` (static metadata, so mixed policies are rejected);
    their parameters are traced leaves, ONE dispatch per ``bits`` value.
    With ``len(fault_lanes) == len(ccfg.p_miss)`` a ``FaultModel.iid(p)``
    lane trains bit-for-bit the ``run_curves`` lane of the same ``p``.
    Runs on one device.
    """
    outs = _run(ccfg, _fault_job(ccfg, fault_lanes))
    return FaultCurveResult(
        config=ccfg, fault_lanes=tuple(fault_lanes),
        acc=_fetch(outs, 6), nll=_fetch(outs, 7),
        loss_history=_fetch(outs, 1, lanes_last=True),
        stale_age=_fetch(outs, 2, np.int64, lanes_last=True),
        dropped_frames=_fetch(outs, 3, np.int64),
        outage_frames=_fetch(outs, 4, np.int64),
        retry_slots=_fetch(outs, 5, np.int64),
        logged_steps=np.asarray(ccfg.logged_steps()),
        params=[o[0] for o in outs])


def run_scheduled_curves(ccfg: CurveConfig, schedule: BitsSchedule
                         ) -> ScheduledCurveResult:
    """Train the ``p_miss`` lanes with a channel-aware ``BitsSchedule``.

    ``schedule.update`` re-chooses the depth every round from the last
    round's accounting, on device: the whole run is ONE dispatch.  Streams
    derive from ``candidates[init_index]``, so ``FixedBits(b)`` trains
    bit-for-bit the ``run_curves(bits=(b,))`` lanes.  Runs on one device.
    """
    [(vals, hist, coll, idx_seq, acc, nll)] = _run(
        ccfg, _sched_job(ccfg, schedule))
    return ScheduledCurveResult(
        config=ccfg, schedule=schedule, p_miss=ccfg.lane_p_miss(),
        acc=np.asarray(acc, np.float64), nll=np.asarray(nll, np.float64),
        loss_history=np.asarray(hist, np.float64).T,
        collision_frac=np.asarray(coll, np.float64),
        bits_per_step=np.asarray(schedule.candidates,
                                 np.int64)[np.asarray(idx_seq)],
        logged_steps=np.asarray(ccfg.logged_steps()), params=vals)


def run_curves_dp(ccfg: CurveConfig, compress: CompressedAllReduce, *,
                  n_devices: Optional[int] = None) -> DPCurveResult:
    """Train the 2-D (p_miss lanes x DP shards) grid with compressed comms.

    Each step splits the batch across ``ccfg.dp_shards`` ranks whose top-k
    gradients (per-rank error feedback) ``compress.reduce`` all-reduces in
    the scan: ONE dispatch per ``bits`` value.  The DP axis lies wholly on
    the device mesh or on a named vmap axis (``shard.dp_mesh_shape``).
    ``repro.sim.results.summarize_dp_curves`` reports uplink + DP bits.
    """
    outs = _run(ccfg, _dp_job(ccfg, compress, n_devices))
    # the analytic per-step bill every measured step must equal
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:],
                                                         x.dtype), outs[-1][0])
    return DPCurveResult(
        config=ccfg, compress=compress, p_miss=ccfg.lane_p_miss(),
        acc=_fetch(outs, 4), nll=_fetch(outs, 5),
        loss_history=_fetch(outs, 1, lanes_last=True),
        dp_payload_bits=_fetch(outs, 2, np.int64, lanes_last=True),
        dp_payload_bits_total=_fetch(outs, 3, np.int64),
        dp_payload_bits_step=int(compress.payload_bits(params)
                                 * ccfg.dp_shards),
        dp_dense_bits_step=int(compress.dense_bits(params) * ccfg.dp_shards),
        logged_steps=np.asarray(ccfg.logged_steps()),
        params=[o[0] for o in outs])

"""The declarative contract registry: every jitted entry point of the repo
declares its performance invariants here, and ``python -m repro.analysis``
(or the tier-1 ``tests/test_analysis.py`` parametrization) enforces them.

A :class:`Contract` names the entry point, the leaves it must stay
recompile-free over, its dispatch bound (documentation for the shared
assertions in :mod:`repro.analysis.contracts` that the benchmarks call),
and which checks apply.  The :attr:`Contract.build` thunk materializes the
actual traceable function + argument factory lazily — builders import the
subsystem locally and construct arguments from ``ShapeDtypeStruct``/
``jax.eval_shape`` stand-ins, so checking a contract never executes a real
training or serving step.  The curve engines take the inputs of their
runner's first dispatch at a tiny config (data and init, made on the host;
:func:`_curve_entry`), so a contract checks what the runner dispatches.

Adding a contract for a new entry point::

    def _build_my_engine() -> Entry:
        from repro.my import engine                    # local import
        fn = engine._make_step(...)                    # the jitted callable
        def argsf(p):                                  # p perturbs the leaf
            return (..., Protocol.ocs(bits=8, p_miss=np.full((N,), p,
                                                             np.float32)), ...)
        return Entry(fn=fn, argsf=argsf)

    CONTRACTS += (Contract(name="my.engine", build=_build_my_engine,
                           recompile_free_over="protocol.p_miss",
                           max_dispatches="1 per run"),)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import contracts as C
from repro.analysis.report import Finding


@dataclasses.dataclass
class Entry:
    """A materialized entry point: the traceable callable + its arguments.

    ``argsf(p)`` embeds the perturbation ``p`` into the contract's
    rebindable leaves (``p_miss``); every other argument must be identical
    across calls.  ``lower`` (optional) produces a ``jax.stages.Lowered``
    for the HLO-level checks; ``donated`` is the donated-buffer count the
    donation check expects in the lowering.
    """

    fn: Callable
    argsf: Callable[[float], Tuple]
    lower: Optional[Callable] = None
    donated: int = 0


@dataclasses.dataclass(frozen=True)
class Contract:
    """One entry point's declared invariants (see module docstring)."""

    name: str
    build: Callable[[], Entry]
    recompile_free_over: str = "protocol.p_miss"   # "" disables the check
    max_dispatches: str = ""                       # documented host bound
    forbid_f64: bool = True
    forbid_host_sync: bool = True
    host_sync_allowlist: Tuple[str, ...] = ()
    check_donation: bool = False
    forbid_collectives: bool = False


# ---------------------------------------------------------------------------
# builders (lazy: subsystem imports stay inside)
# ---------------------------------------------------------------------------

_N_WORKERS = 4          # worker count shared by the tiny vertical builders


def _key_data(*shape) -> np.ndarray:
    """Concrete uint32 key data (raw-key form; no device op to build)."""
    return np.zeros(shape + (2,), np.uint32)


def _build_protocol_aggregate() -> Entry:
    from repro.protocol import Protocol

    h = jax.ShapeDtypeStruct((_N_WORKERS, 2, 8), jnp.float32)
    rng = _key_data()

    def agg(protocol, h, rng):
        return protocol.aggregate(h, rng)

    def argsf(p):
        proto = Protocol.ocs(
            bits=8, max_rounds=2,
            p_miss=np.full((_N_WORKERS,), p, np.float32))
        return (proto, h, rng)

    return Entry(fn=agg, argsf=argsf,
                 lower=lambda: jax.jit(agg).lower(*argsf(0.05)))


def _tiny_curve_config():
    from repro.sim.train_curves import CurveConfig
    return CurveConfig(bits=(8,), p_miss=(0.0, 0.05), steps=4, batch=4,
                       max_rounds=2, n_train=32, n_val=16, hw=8,
                       encoder_dims=(8,), embed_dim=4, head_dims=(8,),
                       log_every=2)


def _curve_entry(job, ccfg, perturb) -> Entry:
    """A curve engine's entry from its runner's own first dispatch (the
    jitted engine and its inputs at the tiny config): ``perturb(lane, p)``
    rebinds the program's lane arguments by name."""
    from repro.sim import train_curves as tc

    fused, params0, opt0, lane, shared = next(tc._dispatches(ccfg, job))

    def argsf(p):
        return (params0, opt0, *{**lane, **perturb(lane, p)}.values(),
                *shared)

    return Entry(fn=fused, argsf=argsf)


def _rebind_p(lane, p):
    return {"p": np.asarray([0.0, p], np.float32)}


def _build_curves_fused() -> Entry:
    from repro.sim import train_curves as tc

    ccfg = _tiny_curve_config()
    return _curve_entry(tc._plain_job(ccfg, 1), ccfg, _rebind_p)


def _build_curves_fused_dp() -> Entry:
    from repro.optim.compressed_allreduce import CompressedAllReduce
    from repro.sim import train_curves as tc

    ccfg = dataclasses.replace(_tiny_curve_config(), dp_shards=2)

    def perturb(lane, p):
        # the perturbation lands in BOTH rebindable state leaves: the lane
        # p_miss axis AND the error-feedback memory values — the EF carry
        # must be ordinary traced data, never a recompile trigger (concrete
        # arrays here, so differing values would show up as differing
        # jaxprs if they were ever baked in)
        return {**_rebind_p(lane, p), "err0": jax.tree.map(
            lambda x: np.full(x.shape, p, np.float32), lane["err0"])}

    job = tc._dp_job(ccfg, CompressedAllReduce.topk(0.25), 1)
    return _curve_entry(job, ccfg, perturb)


def _build_curves_sched() -> Entry:
    from repro.protocol import CollisionAdaptiveBits
    from repro.sim import train_curves as tc

    ccfg = _tiny_curve_config()
    job = tc._sched_job(ccfg, CollisionAdaptiveBits((8, 16)))
    return _curve_entry(job, ccfg, _rebind_p)


def _build_serve_tick() -> Entry:
    from repro import faults
    from repro.configs import get_reduced
    from repro.models import model as M
    from repro.parallel.sharding import split_tree
    from repro.protocol import Protocol
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_reduced("qwen1.5-0.5b", n_layers=1, d_model=8, n_heads=2,
                      n_kv_heads=2, d_ff=16, vocab_size=32, n_workers=2)
    m = M.build(cfg)
    values = jax.eval_shape(lambda k: split_tree(m.init(k))[0],
                            jax.random.PRNGKey(0))
    eng = ServeEngine(m, values, ServeConfig(batch_slots=2, max_seq=8))

    def argsf(p):
        # the perturbation lands in EVERY rebindable channel leaf at once:
        # protocol.p_miss, the Gilbert–Elliott transition/miss probs, the
        # dropout rates, AND the carried chain state (bad mask, stale
        # cache, outage counters) — a fault sweep must reuse the one
        # compiled tick
        proto = Protocol.ocs(bits=8, max_rounds=2,
                             p_miss=np.full((2,), p, np.float32))
        fm = faults.FaultModel.gilbert_elliott(
            p_gb=p, p_bg=2 * p, p_miss_good=p, p_miss_bad=0.5,
            policy=faults.DegradePolicy.stale()).with_dropout(p, 1.0 - p)
        fstate = faults.FaultState(
            bad=np.arange(2) % 2 == int(p > 0.05),
            offline=np.zeros((2,), bool),
            stale=np.float32(p), age=np.int32(int(100 * p)),
            consec=np.int32(0))
        return (values, proto, fm, fstate, eng.cur_token, eng.positions,
                eng.cache, np.int32(0))

    return Entry(fn=eng._tick, argsf=argsf,
                 lower=lambda: eng._tick.lower(*argsf(0.05)))


def _build_faults_aggregate() -> Entry:
    from repro import faults
    from repro.protocol import Protocol

    h = jax.ShapeDtypeStruct((_N_WORKERS, 2, 8), jnp.float32)
    rng = _key_data()

    def agg(protocol, model, state, h, rng):
        return faults.aggregate(protocol, model, state, h, rng)

    def argsf(p):
        proto = Protocol.ocs(
            bits=8, max_rounds=2,
            p_miss=np.full((_N_WORKERS,), p, np.float32))
        fm = faults.FaultModel(
            p_gb=np.float32(p), p_bg=np.float32(2 * p),
            p_miss_good=np.float32(p / 2),
            p_miss_bad=np.float32(0.4 + p),
            p_drop=np.float32(p), p_recover=np.float32(1.0 - p),
            policy=faults.DegradePolicy.stale())
        state = faults.FaultState(
            bad=np.arange(_N_WORKERS) % 2 == int(p > 0.05),
            offline=np.arange(_N_WORKERS) % 3 == int(p > 0.05),
            stale=np.full((2, 8), p, np.float32),
            age=np.int32(int(100 * p)), consec=np.int32(int(10 * p)))
        return (proto, fm, state, h, rng)

    return Entry(fn=agg, argsf=argsf,
                 lower=lambda: jax.jit(agg).lower(*argsf(0.05)))


def _build_curves_fused_faults() -> Entry:
    from repro import faults
    from repro.sim import train_curves as tc

    ccfg = _tiny_curve_config()
    lanes, n = 2, ccfg.n_workers
    policy = faults.DegradePolicy.stale()

    def perturb(lane, p):
        # lane-stacked fault grid: both lanes' GE transition probs, dropout
        # rates AND the carried chain state (bad/offline masks, stale
        # cache) move with p — the fused engine must hold at one trace
        fm = faults.FaultModel(
            p_gb=np.asarray([0.0, p], np.float32),
            p_bg=np.asarray([0.25, 2 * p], np.float32),
            p_miss_good=np.asarray([0.0, p], np.float32),
            p_miss_bad=np.asarray([0.5, 0.4 + p], np.float32),
            p_drop=np.asarray([0.0, p], np.float32),
            p_recover=np.asarray([1.0, 1.0 - p], np.float32),
            policy=policy)
        fs0 = faults.FaultState(
            bad=np.zeros((lanes, n), bool),
            offline=(np.arange(lanes * n).reshape(lanes, n) % 3
                     == int(p > 0.05)),
            stale=np.full((lanes, ccfg.batch, ccfg.embed_dim), p,
                          np.float32),
            age=np.zeros((lanes,), np.int32),
            consec=np.zeros((lanes,), np.int32))
        return {"fm": fm, "fs0": fs0}

    job = tc._fault_job(ccfg, [faults.FaultModel.iid(0.0, policy=policy)]
                        * lanes)
    return _curve_entry(job, ccfg, perturb)


def _build_sweep_noisy() -> Entry:
    from repro.sim import sweep as sweep_mod

    fn = functools.partial(sweep_mod._sweep_noisy, bits=8, max_id_bits=2,
                           max_rounds=2, backend="scan", n_devices=1)
    s, r = 2, 1
    h = jax.ShapeDtypeStruct((s, r, _N_WORKERS, 8), jnp.float32)
    mask = jax.ShapeDtypeStruct((s, _N_WORKERS), jnp.bool_)
    id_bits = np.full((s,), 2, np.int32)
    rng = _key_data(s, r)
    n_channels = np.ones((s,), np.int32)

    def argsf(p):
        p_miss = np.full((s, _N_WORKERS), p, np.float32)
        return (h, mask, id_bits, rng, p_miss, n_channels)

    return Entry(fn=fn, argsf=argsf)


def _build_train_step_donated() -> Entry:
    from repro.core import vertical
    from repro.core.vertical import VerticalConfig
    from repro.optim import optimizers, schedules
    from repro.protocol import Protocol
    from repro.train.train_step import make_train_step

    vcfg = VerticalConfig(
        n_workers=_N_WORKERS, input_dim=16, encoder_dims=(8,), embed_dim=4,
        head_dims=(8,), output_dim=4, task="classification",
        aggregation=Protocol.ideal_max(8, tie_break="first"))

    def loss(values, batch):
        views, labels = batch
        return vertical.loss_fn(vcfg, values, views, labels)

    opt = optimizers.adamw(schedules.constant(1e-3), weight_decay=0.01)
    step = make_train_step(loss, opt, donate=True)
    values = jax.eval_shape(lambda k: vertical.init(vcfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, values)
    batch = (jax.ShapeDtypeStruct((_N_WORKERS, 8, 16), jnp.float32),
             jax.ShapeDtypeStruct((8,), jnp.int32))
    args = (values, opt_state, batch)
    donated = len(jax.tree_util.tree_leaves((values, opt_state)))

    return Entry(fn=step, argsf=lambda p: args,
                 lower=lambda: step.lower(*args), donated=donated)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

CONTRACTS: Tuple[Contract, ...] = (
    Contract(
        name="protocol.aggregate",
        build=_build_protocol_aggregate,
        max_dispatches="inline (no host loop)",
        forbid_collectives=True,
    ),
    Contract(
        name="curves.fused",
        build=_build_curves_fused,
        max_dispatches="1 per bits value "
                       "(+ <= ceil(steps/log_every)+2 result fetches)",
    ),
    Contract(
        name="curves.fused_dp",
        build=_build_curves_fused_dp,
        recompile_free_over="protocol.p_miss + error-feedback memory",
        max_dispatches="1 per bits value "
                       "(+ <= ceil(steps/log_every)+2 result fetches)",
    ),
    Contract(
        name="curves.sched",
        build=_build_curves_sched,
        max_dispatches="1 per scheduled run",
    ),
    Contract(
        name="serve.tick",
        build=_build_serve_tick,
        recompile_free_over="protocol.p_miss + fault-model leaves + "
                            "chain state",
        max_dispatches="1 per decode tick",
        forbid_collectives=True,
    ),
    Contract(
        name="faults.aggregate",
        build=_build_faults_aggregate,
        recompile_free_over="GE transition/miss probs + dropout rates + "
                            "chain state + protocol.p_miss",
        max_dispatches="inline (no host loop)",
        forbid_collectives=True,
    ),
    Contract(
        name="curves.fused_faults",
        build=_build_curves_fused_faults,
        recompile_free_over="fault-model leaves + FaultState carry "
                            "(incl. stale cache + dropout masks)",
        max_dispatches="1 per bits value (+ result fetches)",
    ),
    Contract(
        name="sweep.noisy",
        build=_build_sweep_noisy,
        max_dispatches="1 per bits value",
    ),
    Contract(
        name="train.step_donated",
        build=_build_train_step_donated,
        recompile_free_over="",          # no channel leaf: ideal protocol
        max_dispatches="1 per step",
        check_donation=True,
    ),
)


def contract_names() -> Tuple[str, ...]:
    return tuple(c.name for c in CONTRACTS)


def get_contract(name: str) -> Contract:
    for c in CONTRACTS:
        if c.name == name:
            return c
    raise KeyError(f"no contract named {name!r}; "
                   f"known: {contract_names()}")


def check_contract(contract: Contract, *, skip_hlo: bool = False
                   ) -> List[Finding]:
    """Run every check the contract declares; returns its findings."""
    entry = contract.build()
    findings: List[Finding] = []
    if contract.recompile_free_over:
        findings += C.check_trace_stable(contract.name, entry.fn,
                                         entry.argsf)
    if contract.forbid_host_sync:
        findings += C.check_no_host_sync(contract.name, entry.fn,
                                         entry.argsf(0.05),
                                         contract.host_sync_allowlist)
    if contract.forbid_f64:
        findings += C.check_no_f64(contract.name, entry.fn, entry.argsf)
    if contract.check_donation and entry.lower is not None:
        findings += C.check_donation(contract.name, entry.fn,
                                     entry.argsf(0.05), entry.donated)
    if not skip_hlo and entry.lower is not None:
        from repro.analysis import hlo_checks
        findings += hlo_checks.check_entry_hlo(contract, entry)
    return findings


def check_all(*, skip_hlo: bool = False) -> List[Finding]:
    findings: List[Finding] = []
    for c in CONTRACTS:
        findings += check_contract(c, skip_hlo=skip_hlo)
    return findings

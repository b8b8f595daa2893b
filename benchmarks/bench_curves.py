"""Channel-in-the-loop training-curve benchmark: accuracy vs channel quality.

The paper's end-to-end experiment — train the vertical learner with the
noisy-OCS channel *in the forward pass* and report accuracy as a function of
the sensing-miss probability and the backoff depth.  Every ``p_miss`` lane
of a ``bits`` value trains inside ONE compiled train step (each lane carries
its own traced ``repro.protocol.Protocol`` pytree), and the fused scan
driver runs the whole steps loop in ONE dispatch per ``bits`` value.  The
run drives the plain, scheduled (``CollisionAdaptiveBits``), DP and fault
lane programs of the engine, emits their accuracy and comm rows, and
self-checks the engine contracts:

  * exactly one fused compilation AND ``<= ceil(steps/log_every) + 2``
    dispatches per ``bits`` value,
  * the ``p_miss=0`` lane matches the ideal ``Protocol.ideal_max(bits)``
    reference run bit for bit (accuracy AND trained parameters),
  * trajectories unchanged under the Protocol API: a ``FixedBits(bits[0])``
    scheduled run reproduces the plain run's first-bits noisy lanes bit for
    bit (accuracy, nll, loss history AND trained parameters),
  * the adaptive schedule runs end-to-end in ONE ``sched`` dispatch with
    every chosen depth drawn from its candidate set,
  * the 2-D compressed-comms engine (``run_curves_dp``: p_miss lanes x DP
    shards, ``CompressedAllReduce`` inside the fused scan) stays one
    dispatch per ``bits`` value and its MEASURED per-step DP payload bits
    equal the analytic exact-k bill — the unified uplink + DP accounting
    lands in the emitted records (``total_comm_bits``),
  * the fault-injection engine (``run_fault_curves``: Gilbert–Elliott burst
    lanes + worker dropout, ``repro.faults``) adds ZERO extra traces —
    one compile and one dispatch per ``bits`` value however many fault
    lanes ride along — and its ``FaultModel.iid`` witness lane reproduces
    the plain engine's lane 0 bit for bit.

It times nothing: speed is measured on the chip, by ``bench/run.py``.

  PYTHONPATH=src python -m benchmarks.bench_curves           # full curves
  PYTHONPATH=src python -m benchmarks.bench_curves --smoke   # CI smoke tier
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import List, Optional

import numpy as np

from repro import analysis, faults
from repro.optim.compressed_allreduce import CompressedAllReduce
from repro.protocol import CollisionAdaptiveBits, FixedBits
from repro.sim import results as sim_results
from repro.sim import train_curves as tc

# the DP compression operating point both tiers bench: 1/8 kept + EF
_DP_K_FRAC = 1 / 8
_DP_SHARDS = 2


def _fault_lanes(ccfg: tc.CurveConfig):
    """The benched fault grid: one i.i.d. witness lane per ``p_miss`` entry
    position, then burst lanes of growing mean length.  Lane 0 is
    ``FaultModel.iid(p_miss[0])`` so it must reproduce the plain engine's
    lane 0 bit for bit (same stream derivation); the burst lanes share one
    ``stale`` policy — the whole grid is ONE compile per bits value."""
    policy = faults.DegradePolicy.stale()
    models = [faults.FaultModel.iid(p, policy=policy) for p in ccfg.p_miss]
    for burst_len in (4.0, 16.0):
        models.append(faults.FaultModel.burst(
            burst_len=burst_len, gap_len=4 * burst_len, p_miss_bad=0.5,
            p_miss_good=0.01, policy=policy))
    return models


def _smoke_config() -> tc.CurveConfig:
    return tc.CurveConfig(bits=(8, 16), p_miss=(0.0, 0.05, 0.2), steps=24,
                          batch=32, n_train=512, n_val=256, log_every=8)


def _full_config() -> tc.CurveConfig:
    # bench_table1's task scale: large enough that embedding-level fusion
    # actually learns the relation, so the curve has headroom to degrade
    return tc.CurveConfig(bits=(8, 16), p_miss=(0.0, 0.01, 0.02, 0.05, 0.1),
                          steps=600, batch=64, n_train=8192, n_val=512,
                          hw=32, encoder_dims=(128, 64), embed_dim=32,
                          head_dims=(128, 64), log_every=25)


def _run_engine(ccfg: tc.CurveConfig):
    tc.reset_trace_counts()
    tc.reset_dispatch_counts()
    curves = tc.run_curves(ccfg)
    return curves, tc.trace_counts(), tc.dispatch_counts()


def _assert_sched_matches_lanes(sched: tc.ScheduledCurveResult,
                                curves: tc.CurveResult, bi: int) -> None:
    """FixedBits(bits[bi]) scheduled run == plain run's bits[bi] noisy lanes."""
    import jax

    if not np.array_equal(sched.acc, curves.acc[bi]):
        raise RuntimeError(
            "scheduled-engine parity broken: FixedBits accuracy diverged "
            "from the plain fused run")
    if not np.array_equal(sched.nll, curves.nll[bi]):
        raise RuntimeError("scheduled-engine parity broken: nll diverged")
    if not np.array_equal(sched.loss_history, curves.loss_history[bi]):
        raise RuntimeError(
            "scheduled-engine parity broken: loss history diverged")
    for x, y in zip(jax.tree.leaves(sched.params),
                    jax.tree.leaves(curves.noisy_params[bi])):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            raise RuntimeError(
                "scheduled-engine parity broken: trained params diverged")


def run(smoke: bool = False, json_path: Optional[str] = None) -> List[str]:
    ccfg = _smoke_config() if smoke else _full_config()
    n_bits = len(ccfg.bits)

    # the engine contracts are the shared repro.analysis assertions (the
    # same bounds the contract registry documents)
    curves, traces_s, disp_s = _run_engine(ccfg)
    analysis.assert_trace_count(traces_s["fused"], n_bits, "fused engine")
    per_bits_scan = disp_s["fused"] / n_bits
    bound = analysis.fused_dispatch_bound(ccfg.steps, ccfg.log_every)
    analysis.assert_fused_dispatches(per_bits_scan, ccfg.steps,
                                     ccfg.log_every)

    # p_miss lane 0 is 0.0 in both configs: it must reproduce the ideal
    # Protocol.ideal_max(bits) run bit for bit (params and accuracy).
    assert ccfg.p_miss[0] == 0.0
    import jax
    for bi, bits in enumerate(ccfg.bits):
        if curves.acc[bi, 0] != curves.acc_ideal[bi]:
            raise RuntimeError(
                f"bits={bits}: p_miss=0 accuracy {curves.acc[bi, 0]} != "
                f"ideal max_q{bits} accuracy {curves.acc_ideal[bi]}")
        for a, b in zip(jax.tree.leaves(curves.noisy_params[bi]),
                        jax.tree.leaves(curves.ideal_params[bi])):
            if not np.array_equal(np.asarray(a)[0], np.asarray(b)[0]):
                raise RuntimeError(
                    f"bits={bits}: p_miss=0 trained params diverged from "
                    "the ideal reference run")

    # the BitsSchedule hook: a FixedBits schedule must reproduce the plain
    # engine bit for bit (trajectory unchanged under the scheduled API) ...
    tc.reset_dispatch_counts()
    fixed = tc.run_scheduled_curves(ccfg, FixedBits(ccfg.bits[0]))
    analysis.assert_single_dispatch(tc.dispatch_counts(), "sched",
                                    "FixedBits scheduled run")
    _assert_sched_matches_lanes(fixed, curves, bi=0)

    # ... and the collision-adaptive policy runs end-to-end in ONE dispatch
    schedule = CollisionAdaptiveBits(tuple(ccfg.bits))
    tc.reset_dispatch_counts()
    adaptive = tc.run_scheduled_curves(ccfg, schedule)
    analysis.assert_single_dispatch(tc.dispatch_counts(), "sched",
                                    "adaptive scheduled run")
    if not set(np.unique(adaptive.bits_per_step)) <= set(ccfg.bits):
        raise RuntimeError(
            f"schedule chose depths {np.unique(adaptive.bits_per_step)} "
            f"outside its candidates {ccfg.bits}")
    if not np.isfinite(adaptive.acc).all():
        raise RuntimeError("adaptive scheduled run produced non-finite acc")
    sched_switches = int(np.sum(np.diff(adaptive.bits_per_step) != 0))

    # the 2-D compressed-comms engine: p_miss lanes x DP batch shards with
    # CompressedAllReduce (top-k + EF) inside the fused scan — still one
    # dispatch per bits value, and the payload bits MEASURED on device must
    # equal the analytic exact-k bill every step
    dcfg = dataclasses.replace(ccfg, dp_shards=_DP_SHARDS)
    car = CompressedAllReduce.topk(_DP_K_FRAC)
    tc.reset_trace_counts()
    tc.reset_dispatch_counts()
    dp = tc.run_curves_dp(dcfg, car)
    traces_d, disp_d = tc.trace_counts(), tc.dispatch_counts()
    analysis.assert_trace_count(traces_d["fused_dp"], n_bits,
                                "dp curve engine")
    per_bits_dp = disp_d["fused_dp"] / n_bits
    analysis.assert_fused_dispatches(per_bits_dp, ccfg.steps, ccfg.log_every)
    if not np.all(dp.dp_payload_bits == dp.dp_payload_bits_step):
        raise RuntimeError(
            "dp accounting broken: measured per-step payload bits "
            f"{np.unique(dp.dp_payload_bits)} != analytic exact-k bill "
            f"{dp.dp_payload_bits_step}")
    if not np.all(dp.dp_payload_bits_total
                  == dp.dp_payload_bits_step * ccfg.steps):
        raise RuntimeError("dp accounting broken: run total != steps x bill")
    if not np.isfinite(dp.acc).all():
        raise RuntimeError("dp curve run produced non-finite accuracy")

    # the fault-injection engine: FaultModel lanes (Gilbert–Elliott bursts +
    # i.i.d. witnesses) inside the fused scan — the burst-lane self-check:
    # fault lanes add ZERO extra traces (one compile per bits value, however
    # many fault lanes ride along), and the i.i.d. witness lane reproduces
    # the plain engine's lane 0 bit for bit
    flanes = _fault_lanes(ccfg)
    tc.reset_trace_counts()
    tc.reset_dispatch_counts()
    fc = tc.run_fault_curves(ccfg, flanes)
    traces_f, disp_f = tc.trace_counts(), tc.dispatch_counts()
    analysis.assert_trace_count(traces_f["fused_faults"], n_bits,
                                "fault curve engine")
    if disp_f["fused_faults"] != n_bits:
        raise RuntimeError(
            f"fault engine dispatched {disp_f['fused_faults']} times for "
            f"{n_bits} bits values — fault lanes must ride the one fused "
            f"dispatch")
    if not np.array_equal(fc.acc[:, 0], curves.acc[:, 0]):
        raise RuntimeError(
            "fault-engine parity broken: the FaultModel.iid witness lane "
            f"diverged from the plain run (fault {fc.acc[:, 0]} vs plain "
            f"{curves.acc[:, 0]})")
    if not np.isfinite(fc.acc).all():
        raise RuntimeError("fault curve run produced non-finite accuracy")

    records = sim_results.summarize_curves(curves)
    dp_records = sim_results.summarize_dp_curves(dp)
    fault_records = sim_results.summarize_fault_curves(fc)
    rows = sim_results.curve_rows(records)
    rows += sim_results.dp_curve_rows(dp_records)
    rows += sim_results.fault_curve_rows(fault_records)
    rows.append(
        f"curves/engine_scan,0,dispatches_per_bits="
        f"{per_bits_scan:g};compiles={traces_s['fused']}")
    rows.append(
        f"curves/engine_sched,0,dispatches=1;"
        f"candidates={'|'.join(str(b) for b in ccfg.bits)};"
        f"switches={sched_switches};"
        f"final_bits={int(adaptive.bits_per_step[-1])}")
    rows.append(
        f"curves/engine_dp,0,dispatches_per_bits={per_bits_dp:g};"
        f"compiles={traces_d['fused_dp']};dp_shards={_DP_SHARDS};"
        f"k_frac={_DP_K_FRAC:g};"
        f"dp_payload_bits_step={dp.dp_payload_bits_step};"
        f"dp_payload_frac="
        f"{dp.dp_payload_bits_step / dp.dp_dense_bits_step:.3f}")
    rows.append(
        f"curves/engine_faults,0,fault_lanes={len(flanes)};"
        f"dispatches_per_bits={disp_f['fused_faults'] / n_bits:g};"
        f"compiles={traces_f['fused_faults']};"
        f"policy={flanes[0].policy.kind};"
        f"iid_witness_bitwise_equal=1")
    rows.append(
        f"curves/dispatch,0,scan_bound={bound};"
        f"dispatches_per_bits={per_bits_scan:g}")
    rows.append(
        f"curves/meta,0,"
        f"bits={n_bits};lanes={len(ccfg.p_miss)};steps={ccfg.steps};"
        f"p0_matches_ideal=1;fixed_schedule_bitwise_equal=1;"
        f"dp_payload_measured_equals_analytic=1")

    if json_path:
        with open(json_path, "w") as f:
            json.dump(records + dp_records + fault_records, f, indent=2,
                      sort_keys=True)
            f.write("\n")
    return rows


if __name__ == "__main__":
    argv = sys.argv[1:]
    positional = [a for a in argv if a != "--smoke"]
    for r in run(smoke="--smoke" in argv,
                 json_path=positional[0] if positional else None):
        print(r)

"""Channel-in-the-loop training curves (ISSUE 2 + ISSUE 4 + ISSUE 5).

Contracts under test:
  * the fused scan engine trains a whole curve grid in ONE compiled dispatch
    per ``bits`` value (trace + dispatch counters, ``<= ceil(steps/
    log_every) + 2`` per-bits bound), each lane carrying its own traced
    ``repro.protocol.Protocol`` pytree, and is deterministic run-to-run
    (the legacy per-step python driver is gone; its parity contract lives
    on as the FixedBits-schedule bitwise equivalence in
    ``tests/test_protocol.py``);
  * every runner of the one engine (plain, fault, scheduled, DP) traces
    once and dispatches once per ``bits`` value, and its dispatch takes
    other lane leaves without another trace;
  * the ``p_miss`` lane axis shards over local devices bit-for-bit
    (forced-host-device subprocess, mirroring the sweep-engine property);
  * the ``p_miss=0`` lane is bit-for-bit the ideal
    ``Protocol.ideal_max(bits)`` run — trained parameters and evaluated
    accuracy;
  * record/row emission through ``repro.sim.results``;
  * the rng-threaded train step (its channel state now the ``(key,
    Protocol)`` tuple), donated train-state carries, and the trainer hook
    behind the curve runner.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import vertical
from repro.core.vertical import VerticalConfig
from repro.optim import optimizers, schedules
from repro.optim.compressed_allreduce import CompressedAllReduce
from repro.protocol import CollisionAdaptiveBits, Protocol
from repro.sim import results as sim_results
from repro.sim import train_curves as tc
from repro.train.train_step import make_train_step
from repro.train.trainer import TrainerConfig, train

TINY = tc.CurveConfig(bits=(8,), p_miss=(0.0, 0.3), steps=8, batch=16,
                      n_train=128, n_val=64, hw=8, encoder_dims=(8,),
                      embed_dim=8, head_dims=(8,), log_every=4)


def _leaves_equal(a, b, lane=0):
    return all(np.array_equal(np.asarray(x)[lane], np.asarray(y)[lane])
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_scan_engine_one_dispatch_per_bits_value():
    """The fused engine compiles once AND dispatches once per bits value —
    the whole steps loop, the ideal reference and both evals included."""
    cfg = dataclasses.replace(TINY, bits=(8, 16))
    tc.reset_trace_counts()
    tc.reset_dispatch_counts()
    tc.run_curves(cfg)
    traces, disp = tc.trace_counts(), tc.dispatch_counts()
    assert traces["fused"] == 2, traces
    assert disp["fused"] == 2, disp
    # nothing fell back to another driver
    assert all(v == 0 for k, v in disp.items() if k != "fused"), disp
    # the ISSUE bound: <= ceil(steps/log_every) + 2 dispatches per bits
    bound = math.ceil(cfg.steps / cfg.log_every) + 2
    assert disp["fused"] / len(cfg.bits) <= bound


def _fault_grid(p):
    from repro.faults import DegradePolicy, FaultModel
    return [FaultModel.iid(0.0, policy=DegradePolicy.stale()),
            FaultModel.burst(burst_len=2.0 + 10 * p, gap_len=8.0,
                             p_miss_bad=0.5, p_miss_good=p,
                             policy=DegradePolicy.stale())]


def _p_rebind(lane, p):
    return {"p": jnp.asarray([0.0, p], jnp.float32)}


def _fault_rebind(lane, p):
    return {"fm": jax.tree.map(lambda *xs: jnp.stack(xs), *_fault_grid(p))}


_SCHED = CollisionAdaptiveBits((8, 16), escalate=0.01)
_CAR = CompressedAllReduce.topk(1 / 8)
_RUNNERS = {   # public runner, its job, the lane leaves a rebind changes
    "fused": (lambda c: tc.run_curves(c, n_devices=1),
              lambda c: tc._plain_job(c, 1), _p_rebind),
    "fused_faults": (lambda c: tc.run_fault_curves(c, _fault_grid(0.05)),
                     lambda c: tc._fault_job(c, _fault_grid(0.05)),
                     _fault_rebind),
    "sched": (lambda c: tc.run_scheduled_curves(c, _SCHED),
              lambda c: tc._sched_job(c, _SCHED), _p_rebind),
    "fused_dp": (lambda c: tc.run_curves_dp(c, _CAR, n_devices=1),
                 lambda c: tc._dp_job(c, _CAR, 1), _p_rebind),
}


@pytest.mark.parametrize("name", sorted(_RUNNERS))
def test_runner_traces_once_and_rebinds_lane_leaves(name):
    """Each runner traces its engine once and dispatches once per bits
    value (the scheduled run: once in all), and its dispatch takes other
    p_miss or fault leaves without another trace."""
    run, job, rebind = _RUNNERS[name]
    cfg = dataclasses.replace(TINY, bits=(8, 16), steps=4, log_every=2,
                              dp_shards=2)
    n = 1 if name == "sched" else len(cfg.bits)
    want = {k: n if k == name else 0 for k in tc.trace_counts()}
    tc.reset_trace_counts()
    tc.reset_dispatch_counts()
    run(cfg)
    assert tc.trace_counts() == want
    assert tc.dispatch_counts() == want

    fused, params0, opt0, lane, shared = next(
        tc._dispatches(cfg, job(cfg)))
    tc.reset_trace_counts()
    for p in (0.1, 0.2):
        jax.block_until_ready(fused(
            params0, opt0, *{**lane, **rebind(lane, p)}.values(), *shared))
    assert tc.trace_counts()[name] == 1


def test_heterogeneous_lane_grid_trains_deterministically():
    """Scan-engine invariant (absorbed from the removed python-engine
    parity suite): a grid mixing scalar and per-worker near/far lanes
    trains to identical results on repeat runs — the whole trajectory is a
    pure function of the config's key streams."""
    grid = dataclasses.replace(TINY,
                               p_miss=(0.0, (0.0, 0.1, 0.1, 0.3), 0.3))
    a = tc.run_curves(grid)
    b = tc.run_curves(grid)
    assert np.array_equal(a.acc, b.acc)
    assert np.array_equal(a.nll, b.nll)
    assert np.array_equal(a.acc_ideal, b.acc_ideal)
    assert np.array_equal(a.nll_ideal, b.nll_ideal)
    assert np.array_equal(a.loss_history, b.loss_history)
    assert np.array_equal(a.ideal_loss_history, b.ideal_loss_history)
    assert np.array_equal(a.logged_steps, b.logged_steps)
    for pa, pb in ((a.noisy_params, b.noisy_params),
                   (a.ideal_params, b.ideal_params)):
        for x, y in zip(jax.tree.leaves(pa[0]), jax.tree.leaves(pb[0])):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    # the noisy lanes really did see different channels (lane 0 vs lane 2)
    assert not np.array_equal(a.loss_history[0, :, 0],
                              a.loss_history[0, :, 2])


def test_sharded_curve_lanes_match_vmap_path():
    """p_miss-lane shard_map over >=2 forced host devices is bit-for-bit
    identical to the single-device vmap path — including a lane count that
    does not divide the device count (padding lanes dropped) and a
    per-worker heterogeneous lane."""
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        assert jax.local_device_count() == 4, jax.devices()
        from repro.sim import train_curves as tc
        # 3 lanes: not divisible by 4 nor by 2 -> padding on both meshes
        cfg = tc.CurveConfig(bits=(8,), p_miss=(0.0, (0.0, 0.1, 0.1, 0.3),
                                                0.3),
                             steps=6, batch=16, n_train=128, n_val=64, hw=8,
                             encoder_dims=(8,), embed_dim=8, head_dims=(8,),
                             log_every=3)
        ref = tc.run_curves(cfg, n_devices=1)
        for n_dev in (None, 2, 4):     # None = auto-detect (4 devices)
            got = tc.run_curves(cfg, n_devices=n_dev)
            assert np.array_equal(ref.acc, got.acc), n_dev
            assert np.array_equal(ref.nll, got.nll), n_dev
            assert np.array_equal(ref.loss_history, got.loss_history), n_dev
            for pa, pb in ((ref.noisy_params, got.noisy_params),
                           (ref.ideal_params, got.ideal_params)):
                for x, y in zip(jax.tree.leaves(pa[0]),
                                jax.tree.leaves(pb[0])):
                    assert np.array_equal(np.asarray(x), np.asarray(y)), n_dev
        print("SHARDED_CURVES_OK")
    """)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    assert "SHARDED_CURVES_OK" in proc.stdout


def test_zero_miss_lane_matches_ideal_run_bit_for_bit():
    out = tc.run_curves(TINY)
    assert out.p_miss[0] == 0.0
    # the traced lane array is what the result reports (float32, not a
    # float64 re-derivation)
    assert out.p_miss.dtype == np.float32
    assert np.array_equal(out.p_miss, TINY.lane_p_miss())
    # trained parameters: lane 0 of the noisy run == the ideal max_q8 run
    assert _leaves_equal(out.noisy_params[0], out.ideal_params[0], lane=0)
    assert out.acc[0, 0] == out.acc_ideal[0]
    assert out.nll[0, 0] == out.nll_ideal[0]
    # the logged loss history coincides too (same compiled-math trajectory)
    assert np.array_equal(out.loss_history[0, :, 0],
                          out.ideal_loss_history[0])
    # the deterministic ideal reference trains a single vmap lane
    assert jax.tree.leaves(out.ideal_params[0])[0].shape[0] == 1


def test_curve_records_and_rows(tmp_path):
    out = tc.run_curves(TINY)
    recs = sim_results.summarize_curves(out)
    assert len(recs) == len(TINY.bits) * len(TINY.p_miss)
    r0 = recs[0]
    assert r0["bits"] == 8 and r0["p_miss"] == 0.0
    assert r0["acc"] == r0["acc_ideal"] and r0["acc_gap"] == 0.0
    # uplink accounting uses the D-bit payload the winner transmits
    from repro.core import channel
    fed = channel.ocs_load(TINY.n_workers, TINY.embed_dim, bits=8,
                           cfg=channel.ChannelConfig(payload_bits=8))
    assert r0["uplink_bits_fedocs"] == fed.uplink_bits
    rows = sim_results.curve_rows(recs)
    assert len(rows) == len(recs)
    assert rows[0].startswith("curves/b8_p0,")
    sim_results.write_json(recs, str(tmp_path / "curves.json"))
    import json
    loaded = json.loads((tmp_path / "curves.json").read_text())
    assert loaded[1]["p_miss"] == 0.3


def test_run_curves_is_deterministic():
    a = tc.run_curves(TINY)
    b = tc.run_curves(TINY)
    assert np.array_equal(a.acc, b.acc)
    assert _leaves_equal(a.noisy_params[0], b.noisy_params[0], lane=1)


def test_curve_config_validation():
    with pytest.raises(ValueError):
        tc.CurveConfig(bits=(12,))            # no ideal max_q12 reference
    with pytest.raises(ValueError):
        tc.CurveConfig(p_miss=(0.0, 1.0))
    with pytest.raises(ValueError):           # wrong per-worker length
        tc.CurveConfig(p_miss=((0.0, 0.1),))  # n_workers = 4
    with pytest.raises(ValueError):
        tc.CurveConfig(p_miss=(0.0, (0.1, 0.2, 0.3, 1.5)))
    with pytest.raises(ValueError):
        tc.CurveConfig(backend="scan", p_miss=())
    with pytest.raises(TypeError):            # the legacy python driver
        tc.CurveConfig(engine="python")       # is gone (one release passed)


def test_curve_config_protocol_template():
    proto = TINY.protocol(8)
    assert proto.kind == "ocs" and proto.bits == 8
    assert proto.max_rounds == TINY.max_rounds
    assert proto.backend == TINY.backend
    assert proto.p_miss is None               # lanes bind it per call


def test_curve_per_worker_lanes_broadcast():
    """Scalar and per-worker lanes mix: lane_p_miss broadcasts to (L, N)."""
    cfg = dataclasses.replace(TINY, p_miss=(0.0, (0.0, 0.1, 0.1, 0.3)))
    lanes = cfg.lane_p_miss()
    assert lanes.shape == (2, 4)
    assert np.array_equal(lanes[0], np.zeros(4, np.float32))
    # all-scalar configs keep the historical (L,) lane axis
    assert TINY.lane_p_miss().shape == (2,)


@pytest.mark.slow
def test_curve_pallas_backend_matches_scan_bit_for_bit():
    """The fused contention kernel drives the whole training loop to the
    exact same trajectory as the scan backend (tentpole acceptance at the
    train-curve level), including a heterogeneous near/far lane.  Slow
    tier: the fast tier covers the same contract at the aggregator level
    (test_kernels_contention + bench_contention --smoke)."""
    small = dataclasses.replace(TINY, steps=4, n_train=64, n_val=32,
                                p_miss=(0.0, (0.0, 0.1, 0.1, 0.3)))
    a = tc.run_curves(small)
    b = tc.run_curves(dataclasses.replace(small, backend="pallas"))
    assert np.array_equal(a.acc, b.acc)
    assert np.array_equal(a.nll, b.nll)
    assert np.array_equal(a.loss_history, b.loss_history)
    for x, y in zip(jax.tree.leaves(a.noisy_params[0]),
                    jax.tree.leaves(b.noisy_params[0])):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _tiny_step_fixture():
    vcfg = VerticalConfig(n_workers=2, input_dim=4, encoder_dims=(4,),
                          embed_dim=4, head_dims=(4,), output_dim=2,
                          task="classification",
                          aggregation=Protocol.ocs(bits=8))
    params = vertical.init(vcfg, jax.random.PRNGKey(0))
    opt = optimizers.adamw(schedules.linear_warmup_cosine(1e-3, 1, 4))
    views = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 8, 4)).astype(np.float32))
    labels = jnp.zeros((8,), jnp.int32)

    def loss(values, batch, chan):
        v, y = batch                 # batch-leading for microbatch splitting
        rng, proto = chan
        return vertical.loss_fn(vcfg, values, jnp.swapaxes(v, 0, 1), y,
                                rng=rng, protocol=proto)

    batch = (jnp.swapaxes(views, 0, 1), labels)      # (B, N, d)
    chan = (jax.random.PRNGKey(3),
            Protocol.ocs(bits=8, p_miss=jnp.float32(0.2)))
    return params, opt, loss, batch, chan


def test_train_step_with_rng_microbatches():
    """with_rng threading: microbatches receive decorrelated keys (the
    Protocol's p_miss leaf passes through untouched) and the accumulated
    path stays consistent with the single-batch contract."""
    params, opt, loss, batch, chan = _tiny_step_fixture()
    step1 = make_train_step(loss, opt, with_rng=True)
    step2 = make_train_step(loss, opt, microbatches=2, with_rng=True)
    state = opt.init(params)
    v1, _, m1 = jax.jit(step1)(params, state, batch, chan)
    v2, _, m2 = jax.jit(step2)(params, state, batch, chan)
    for m in (m1, m2):
        assert np.isfinite(float(m["loss_mean"]))
    # both produce finite updated params of identical structure
    assert jax.tree.structure(v1) == jax.tree.structure(v2)
    for x in jax.tree.leaves(v2):
        assert np.isfinite(np.asarray(x)).all()


def test_train_step_donated_carries():
    """donate=True: same math, but the params/opt-state input buffers are
    consumed by the dispatch (updated in place, no double-buffering)."""
    params, opt, loss, batch, chan = _tiny_step_fixture()
    plain = make_train_step(loss, opt, with_rng=True)
    v0, s0, _ = jax.jit(plain)(params, opt.init(params), batch, chan)

    donated = make_train_step(loss, opt, with_rng=True, donate=True)
    p_in = jax.tree.map(lambda x: jnp.array(x, copy=True), params)
    s_in = opt.init(p_in)
    in_leaves = jax.tree.leaves((p_in, s_in))
    v1, s1, _ = donated(p_in, s_in, batch, chan)
    for x, y in zip(jax.tree.leaves((v0, s0)), jax.tree.leaves((v1, s1))):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert all(x.is_deleted() for x in in_leaves)


def test_trainer_channel_rng_hook():
    """trainer.train drives a stochastic (max_noisy) loss via
    channel_rng_seed; the run is reproducible step-for-step (and the donated
    carries never consume the caller's init across repeat runs)."""
    proto = Protocol.ocs(bits=8, p_miss=jnp.float32(0.1))
    vcfg = VerticalConfig(n_workers=2, input_dim=4, encoder_dims=(4,),
                          embed_dim=4, head_dims=(4,), output_dim=2,
                          task="classification", aggregation=proto)
    init = vertical.init(vcfg, jax.random.PRNGKey(0))
    opt = optimizers.adamw(schedules.linear_warmup_cosine(1e-3, 1, 4))
    rng = np.random.default_rng(0)
    views = jnp.asarray(rng.standard_normal((2, 8, 4)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 2, (8,)), jnp.int32)

    def loss(values, batch, key):
        v, y = batch
        return vertical.loss_fn(vcfg, values, v, y, rng=key)

    tcfg = TrainerConfig(steps=4, log_every=2, channel_rng_seed=11)
    runs = [train(loss, init, opt, lambda step: (views, labels), tcfg)
            for _ in range(2)]
    assert runs[0].final_step == 4
    assert all(np.isfinite(row["loss_mean"]) for row in runs[0].history)
    for x, y in zip(jax.tree.leaves(runs[0].values),
                    jax.tree.leaves(runs[1].values)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert all(not x.is_deleted() for x in jax.tree.leaves(init))

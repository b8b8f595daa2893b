"""Driver of the fused channel-in-the-loop curve engine
(``repro.sim.train_curves``): the paper's p_miss sweep.

Set-up builds the fused dispatch of the cell's sweep once, makes the
learner's weights from the seed on the device, and drives the dispatch
through its first call (which compiles).  Each dispatch is one whole
training run: every noisy lane and the ideal lane train ``steps`` steps
from the same weights on fresh data and fresh sensing keys, derived from
the seed and the dispatch's index.  The window runs whole dispatches until
its seconds are reached; ``train_samples_per_s`` counts the samples every
lane trained over the window's whole time.

The check replays the set-up dispatch and one window dispatch drawn from
the seed, whole, with the plain reference (``bench/configs/<config>.ref.py``)
on the same weights, rows and sensing streams.  It compares every lane's
loss at the first steps, every lane's trained parameters (the change of
each leaf from the initial weights, by its norm) and every lane's
validation cross-entropy and accuracy after the run.
"""

from __future__ import annotations

import dataclasses
import json
import time
import traceback
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import harness as H
from bench.lib import traffic as T
from bench.lib.trace import capture, find_xplane, load_xplane, reduce_trace


@dataclasses.dataclass
class Engine:
    """The compiled dispatch and what every call of it shares."""

    fused: Callable
    inputs: Callable          # dispatch index -> per-dispatch inputs
    params0: object
    opt0: object
    p_miss: object            # (L,) float32 lane p_miss
    slots: object             # step -> loss-history slot
    lanes: int
    job: dict                 # batch, n_train, steps


def curve_config(cell: H.Cell):
    """The program's ``CurveConfig`` of the cell."""
    from repro.sim import train_curves as tc

    cfg, tr = cell.config, cell.traffic
    agg = cfg["aggregation"]
    return tc.CurveConfig(
        bits=(agg["bits"],), p_miss=tuple(float(p) for p in
                                          T.p_miss_lanes(tr)),
        steps=tr["steps_per_dispatch"], batch=tr["batch"],
        lr=cfg["optimizer"]["lr"], max_rounds=agg["max_rounds"],
        n_train=tr["n_train"], n_val=tr["n_val"],
        n_classes=cfg["n_classes"], grid=cfg["grid"], hw=cfg["hw"],
        sigma=tr["sigma"], encoder_dims=tuple(cfg["encoder_dims"]),
        embed_dim=cfg["embed_dim"], head_dims=tuple(cfg["head_dims"]),
        log_every=tr["log_every"], backend=agg["backend"])


def build(cell: H.Cell, seed: int, fused: Callable = None) -> Engine:
    import jax
    import jax.numpy as jnp

    from repro.core import vertical
    from repro.sim import train_curves as tc

    ccfg = curve_config(cell)
    bits = ccfg.bits[0]
    per_bits = tc._make_steps(ccfg, bits)
    vcfg_n, opt = per_bits[0], per_bits[2]
    logged = ccfg.logged_steps()
    if fused is None:
        fused = tc._make_fused(ccfg, per_bits, len(logged), 1)
    ref = cell.reference()

    base = jax.random.PRNGKey(T.derive_seed(seed, 0))
    bank_key, run_key, w_key = jax.random.split(base, 3)
    params0 = jax.jit(lambda k: ref.init_params(cell.config, k))(w_key)
    want = jax.eval_shape(lambda k: vertical.init(vcfg_n, k), w_key)
    got = jax.eval_shape(lambda: params0)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise ValueError("the learner's parameter layout is not the one "
                         "the configuration's reference makes")
    gen = T.make_patch_task(cell.config, cell.traffic)
    lanes = len(ccfg.p_miss)

    @jax.jit
    def make_inputs(bank_key, run_key, i):
        k = jax.random.fold_in(run_key, i)
        views, labels, vviews, vlabels = gen(bank_key,
                                             jax.random.fold_in(k, 0))
        lane_keys = jax.random.split(jax.random.fold_in(k, 2), lanes)
        return (lane_keys, jax.random.fold_in(k, 1), views, labels, vviews,
                vlabels)

    def inputs(i):
        # the keys are arguments, so one program serves every seed
        return make_inputs(bank_key, run_key, i)

    job = {"batch": ccfg.batch, "n_train": ccfg.n_train,
           "steps": ccfg.steps}
    return Engine(fused=fused, inputs=inputs, params0=params0,
                  opt0=opt.init(params0),
                  p_miss=jnp.asarray(ccfg.lane_p_miss()),
                  slots=jnp.asarray(tc._log_slots(ccfg, logged)),
                  lanes=lanes, job=job)


def dispatch(eng: Engine, i: int) -> Dict[str, object]:
    """One whole curve run.  Its losses, accuracies and cross-entropies
    come to the host (fetching them waits for the dispatch to finish);
    the trained parameters stay on the device under ``params`` and
    ``params_ideal`` (each with a leading lane axis), untouched: the
    window adds no work of its own to the device."""
    import jax

    lane_keys, k_data, views, labels, vviews, vlabels = eng.inputs(i)
    n_out, i_out = eng.fused(eng.params0, eng.opt0, lane_keys, eng.p_miss,
                             k_data, views, labels, vviews, vlabels,
                             eng.slots)
    vals_n, hist_n, acc_n, nll_n = n_out
    vals_i, hist_i, acc_i, nll_i = i_out
    out = jax.device_get({"hist": hist_n, "hist_ideal": hist_i[0],
                          "acc": acc_n, "acc_ideal": acc_i[0],
                          "nll": nll_n, "nll_ideal": nll_i[0]})
    out = {k: np.asarray(v) for k, v in out.items()}
    out["params"], out["params_ideal"] = vals_n, vals_i
    return out


def finite(out: Dict[str, object]) -> bool:
    return all(np.all(np.isfinite(v)) for k, v in out.items()
               if not k.startswith("params"))


def window(eng: Engine, seconds: float, spans: H.Spans, seed: int):
    """Whole dispatches from index 1 until ``seconds`` have passed:
    ``(outputs, picked, start, end)``.  ``outputs`` maps every index to
    its losses and metrics; the trained parameters are kept for one
    dispatch only, ``picked``, drawn from the seed uniformly among all the
    window's dispatches as they come (reservoir sampling)."""
    outs = {}
    rng = np.random.default_rng(T.derive_seed(seed, 3))
    picked, kept = None, None
    i = 1
    with spans("window"):
        t_start = time.perf_counter()
        while True:
            with spans("dispatch"):
                out = dispatch(eng, i)
            if rng.random() * i < 1.0:
                picked, kept = i, (out["params"], out["params_ideal"])
            outs[i] = {k: v for k, v in out.items()
                       if not k.startswith("params")}
            i += 1
            if time.perf_counter() - t_start >= seconds:
                break
        t_end = time.perf_counter()
    outs[picked]["params"], outs[picked]["params_ideal"] = kept
    return outs, picked, t_start, t_end


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

_RUNNERS: Dict[tuple, Callable] = {}


def reference_runner(cell: H.Cell, job: dict, precision: str = "highest",
                     fault: Optional[str] = None) -> Callable:
    """The reference's whole curve run, jitted once per process for each
    configuration, precision and planted fault (``unchanged``: no step
    updates the state; ``half_batch``: each step takes its mean over the
    first half of its rows)."""
    import jax

    key = (json.dumps(cell.config, sort_keys=True),
           json.dumps(job, sort_keys=True), precision, fault)
    if key not in _RUNNERS:
        ref = cell.reference()
        if fault == "unchanged":
            ref.adamw_update = lambda opt, steps, state, params, grads: (
                state, params)
        elif fault == "half_batch":
            loss = ref.loss
            ref.loss = lambda params, views, labels, pool, prec: loss(
                params, views[:, :labels.shape[0] // 2],
                labels[:labels.shape[0] // 2], pool, prec)
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        config = cell.config
        _RUNNERS[key] = jax.jit(
            lambda p0, vw, lb, vv, vl, kd, lk, pm: ref.train(
                config, job, p0, vw, lb, vv, vl, kd, lk, pm, precision))
    return _RUNNERS[key]


def reference_out(cell: H.Cell, eng: Engine, i: int,
                  precision: str = "highest",
                  fault: Optional[str] = None) -> Dict[str, object]:
    """The reference's whole run of dispatch ``i``, in the layout
    :func:`dispatch` gives, with ``grad0`` (first step's gradient norm of
    every leaf, lanes and then the ideal lane) beside it."""
    import jax

    lane_keys, k_data, views, labels, vviews, vlabels = eng.inputs(i)
    noisy, ideal = jax.device_get(reference_runner(
        cell, eng.job, precision, fault)(eng.params0, views, labels, vviews,
                                         vlabels, k_data, lane_keys,
                                         eng.p_miss))
    return {"hist": np.asarray(noisy["loss"]),
            "hist_ideal": np.asarray(ideal["loss"]),
            "acc": np.asarray(noisy["acc"]),
            "acc_ideal": np.asarray(ideal["acc"]),
            "nll": np.asarray(noisy["nll"]),
            "nll_ideal": np.asarray(ideal["nll"]),
            "params": noisy["params"],
            "params_ideal": jax.tree.map(lambda x: x[None], ideal["params"]),
            "grad0": np.concatenate([np.asarray(noisy["grad0"]),
                                     np.asarray(ideal["grad0"])[None]])}


def control_out(cell: H.Cell, eng: Engine, i: int) -> Dict[str, object]:
    """The control: the reference at float8 operands."""
    return reference_out(cell, eng, i, "fp8")


def change_norms(out: Dict[str, object], params0) -> np.ndarray:
    """``(lanes + 1, leaves)``: the norm of each leaf's change from the
    initial weights, noisy lanes first, the ideal lane last."""
    import jax

    p0 = [np.asarray(x, np.float64) for x in jax.tree.leaves(
        jax.device_get(params0))]
    noisy = [np.asarray(x, np.float64) for x in jax.tree.leaves(
        jax.device_get(out["params"]))]
    ideal = [np.asarray(x, np.float64)[0] for x in jax.tree.leaves(
        jax.device_get(out["params_ideal"]))]
    lanes = noisy[0].shape[0]
    rows = [[np.linalg.norm((x[l] - x0).ravel()) for x, x0 in zip(noisy, p0)]
            for l in range(lanes)]
    rows.append([np.linalg.norm((x - x0).ravel())
                 for x, x0 in zip(ideal, p0)])
    return np.asarray(rows)


def gaps(want: Dict[str, object], got: Dict[str, object], params0,
         n_steps: int) -> Dict[str, float]:
    """Every number the check can compare, worst over the lanes.

    ``loss_gap_step<s>``: relative gap of step ``s``'s loss.
    ``param_change_gap``: over leaves and lanes, the gap between the norms
    of the program's and the reference's change of a leaf, against the
    reference's norm of that leaf or of the lane's median leaf, whichever
    is larger; leaves whose first gradient in the reference is under a
    thousandth of the lane's median leaf's are left out.
    ``nll_gap``: relative gap of the validation cross-entropy;
    ``acc_gap``: absolute gap of the validation accuracy."""
    def lanes(out, key, cols=None):
        a = np.asarray(out[key], np.float64)
        b = np.asarray(out[key + "_ideal"], np.float64)
        if cols is not None:
            a, b = a[:, :cols], b[:cols]
        return np.concatenate([a, b[None]])

    def worst(x):
        return float(np.max(np.where(np.isfinite(x), x, np.inf)))

    out = {}
    w, g = lanes(want, "hist", n_steps), lanes(got, "hist", n_steps)
    rel = np.abs(g - w) / np.abs(w)
    for s in range(n_steps):
        out[f"loss_gap_step{s}"] = worst(rel[:, s])
    d_want = change_norms(want, params0)
    d_got = change_norms(got, params0)
    g0 = np.asarray(want["grad0"], np.float64)
    moved = g0 >= 1e-3 * np.median(g0, axis=1, keepdims=True)
    scale = np.maximum(d_want, np.median(d_want, axis=1, keepdims=True))
    out["param_change_gap"] = worst(
        np.where(moved, np.abs(d_got - d_want) / scale, 0.0))
    out["param_leaves_left_out"] = float(np.sum(~moved))
    nw, ng = lanes(want, "nll"), lanes(got, "nll")
    out["nll_gap"] = worst(np.abs(ng - nw) / np.abs(nw))
    out["acc_gap"] = worst(np.abs(lanes(got, "acc") - lanes(want, "acc")))
    return out


def read_gaps(cell: H.Cell, eng: Engine, outs: Dict[int, Dict[str, object]]
              ) -> Dict[str, float]:
    """The worst of :func:`gaps` over the given dispatches."""
    n_steps = cell.workload["check"]["steps"]
    worst: Dict[str, float] = {}
    for i, out in outs.items():
        want = reference_out(cell, eng, i)
        for k, v in gaps(want, out, eng.params0, n_steps).items():
            worst[k] = max(worst.get(k, 0.0), v)
    return worst


def check(cell: H.Cell, eng: Engine, outs: Dict[int, Dict[str, object]]
          ) -> Dict[str, H.Check]:
    """The numbers of :func:`gaps` that have a limit in the workload file
    are compared; the others are logged."""
    got = read_gaps(cell, eng, outs)
    H.log(f"check: {got}")
    return {name: H.Check(got[name], limit)
            for name, limit in cell.workload["check"]["limits"].items()}


def run(cell: H.Cell, *, seed: int, seconds: float, tracing: bool,
        t0: float, clock: H.CompileClock, device: dict) -> H.Run:
    from bench.lib import counts as C

    spans = H.Spans(tracing)
    t_build = time.perf_counter()
    eng = build(cell, seed)
    t_warm = time.perf_counter()
    out0 = dispatch(eng, 0)
    setup = clock.take()
    H.log(f"setup: imports {t_build - t0:.3f} s, build {t_warm - t_build:.3f}"
          f" s, first dispatch {time.perf_counter() - t_warm:.3f} s; {setup}")
    if tracing:
        seconds = min(seconds, cell.workload["trace_seconds"])
        with capture(H.TRACE_DIR):
            outs, picked, t_start, t_end = window(eng, seconds, spans,
                                                  seed)
        summary = reduce_trace(load_xplane(find_xplane(H.TRACE_DIR)))
    else:
        outs, picked, t_start, t_end = window(eng, seconds, spans, seed)
        summary = None
    in_window = clock.take()
    H.log(f"window: {len(outs)} dispatches, {in_window}")
    device = dict(device, memory_peak_bytes=H.memory_peak_bytes())
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)

    lanes = eng.lanes + 1
    samples = len(outs) * eng.job["steps"] * lanes * eng.job["batch"]
    elapsed = t_end - t_start
    error = None
    try:
        t_check = time.perf_counter()
        checks = check(cell, eng, {0: out0, picked: outs[picked]})
        H.log(f"check: dispatches 0 and {picked}, "
              f"{time.perf_counter() - t_check:.3f} s")
    except Exception:                       # the check itself failed
        error = traceback.format_exc()
        checks = {}
    cfg = cell.config
    shapes = dict(n_workers=cfg["grid"] ** 2,
                  input_dim=(cfg["hw"] // cfg["grid"]) ** 2,
                  encoder_dims=cfg["encoder_dims"],
                  embed_dim=cfg["embed_dim"], head_dims=cfg["head_dims"],
                  n_classes=cfg["n_classes"])
    agg, tr = cfg["aggregation"], cell.traffic
    n = cfg["grid"] ** 2
    kernel_work = [  # (calls per dispatch, work per call)
        (eng.job["steps"] * eng.lanes, C.contention_work(
            n, tr["batch"] * cfg["embed_dim"], agg["bits"], C.id_bits(n),
            agg["max_rounds"])),
        (eng.lanes, C.contention_work(
            n, tr["n_val"] * cfg["embed_dim"], agg["bits"], C.id_bits(n),
            agg["max_rounds"]))]
    return H.Run(
        e2e={"train_samples_per_s": samples / elapsed,
             "setup_s": t_start - t0},
        attempted=len(outs),
        failed=sum(not finite(o) for o in outs.values()),
        checks=checks, device=device, summary=summary, error=error,
        layer={"window_s": elapsed, "samples": samples,
               "kernel_units": len(outs),
               "device_kind": device["kind"],
               "flops_per_sample": C.vertical_train_flops(**shapes),
               "kernel_work": kernel_work,
               "compiles_in_window": in_window["compiles"]})


def calibrate(cell: H.Cell, seeds, seconds: float, control_seeds: int = 4):
    """Per seed, every number of :func:`gaps` for the program's set-up
    dispatch and, on the first ``control_seeds`` seeds, for the control
    and for the faults planted in the reference, each against the same
    reference run, on one compiled dispatch."""
    fused = None
    n_steps = cell.workload["check"]["steps"]
    for n, seed in enumerate(seeds):
        eng = build(cell, seed, fused)
        fused = eng.fused
        t = time.perf_counter()
        prog = dispatch(eng, 0)
        jax_block(prog)
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        want = reference_out(cell, eng, 0)
        t_ref = time.perf_counter() - t
        row = {"seed": seed, "program_s": t_prog, "reference_s": t_ref,
               "program": gaps(want, prog, eng.params0, n_steps)}
        if n < control_seeds:
            row["control"] = gaps(want, control_out(cell, eng, 0),
                                  eng.params0, n_steps)
            row["faults"] = {f: gaps(want, reference_out(
                cell, eng, 0, "highest", f), eng.params0, n_steps)
                for f in ("unchanged", "half_batch")}
        yield row


def jax_block(out: Dict[str, object]) -> None:
    import jax

    jax.block_until_ready((out["params"], out["params_ideal"]))

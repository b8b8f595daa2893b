"""Drive both main paths once on a TPU and check what comes out.

  python chip_smoke.py               # phases train + serve, one chip
  python chip_smoke.py --four-chips  # phase four_chips only, four chips

Phase ``train`` is the paper's experiment: the vertical FedOCS learner at
the paper §IV-B widths trained for a few steps with its embeddings pooled
through the noisy-OCS channel (``repro.sim.train_curves.run_curves``,
fused Pallas contention kernel), checked against the ``lax.scan`` backend.
Phase ``serve`` is channel-in-the-loop serving of qwen1.5-0.5b at published
widths with random weights, built the way ``python -m repro.launch.serve``
builds it, checked against a batch-1 reference decode; inside it the
channel aggregate at the decode tick's shape must match the scan backend
bit for bit.  Phase ``four_chips`` runs the 2-D (p_miss lanes x DP ranks)
compressed-all-reduce curve engine on four devices and on one.

Everything is built from the committed files and ``--seed``, in this one
process.  Evidence (device, compile seconds, step and tick times after the
results are on the host, check values) goes to earlier lines; the last line
is one JSON object with ``ok`` and the device.  Any failed check, or a
platform other than ``tpu``, exits non-zero.  Every program handed to the
compiler is dumped as StableHLO under ``.smoke_ir/`` (git-ignored; JAX
writes it before the persistent-cache lookup, so a warm cache dumps too),
where the checks find the Mosaic contention kernel (``tpu_custom_call``),
the partition count and the all-gather.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
IR_DIR = ROOT / ".smoke_ir"

# paper §IV-B widths (configs/fedocs_cifar.py): 2x2 grid = 4 workers
TRAIN_WIDTHS = dict(grid=2, hw=32, n_classes=10, encoder_dims=(256, 128),
                    embed_dim=64, head_dims=(512, 512, 512))
# pallas vs scan curves: same winners, but XLA may fuse the two programs'
# surrounding float ops differently
TRAIN_TOL = 1e-4
# 4 devices vs 1: the DP ranks' matmuls are tiled differently (per device vs
# a vmapped rank axis), so sums associate differently.  Before any update
# (logged step 0) that is float-rounding noise.  Training amplifies it: a
# rounding difference that crosses an 8-bit code boundary changes a
# channel winner or a top-k pick, and over 20 Adam steps the curves drift
# apart by about 1% of the loss (0.012 measured on a v5e).  A broken
# placement (a rank on the wrong batch slice, a missing all-reduce) moves
# the step-0 loss or the trained curves by far more.
DP_STEP0_TOL = 1e-3
DP_TOL = 0.05


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise CheckFailed(what)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (seconds) between resets."""

    def __init__(self, jax):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    def take(self) -> float:
        secs, self.secs = self.secs, 0.0
        return secs


def ir_modules(name: str):
    """StableHLO text of every program called ``name`` handed to the
    compiler so far, in order."""
    return [p.read_text() for p in sorted(IR_DIR.glob(
        f"jax_ir*_{name}_compile.mlir"))]


def check_kernel(modules, what: str) -> None:
    """Some of ``modules`` holds the compiled contention kernel."""
    n = sum("tpu_custom_call" in t for t in modules)
    check(n > 0, f"{what} holds the contention kernel as a tpu_custom_call "
                 f"({n} of {len(modules)} dumped programs)")


# ---------------------------------------------------------------------------
# phase train
# ---------------------------------------------------------------------------

def phase_train(seed: int, clock: CompileClock, *, steps: int = 20,
                batch: int = 256, widths=None, expect_kernel: bool = True):
    import numpy as np

    from repro.sim import train_curves as tc

    ccfg = tc.CurveConfig(bits=(8,), p_miss=(0.0, 0.05, 0.1), steps=steps,
                          batch=batch, backend="pallas", seed=seed,
                          **(widths or TRAIN_WIDTHS))
    runs = {}
    for name, cfg in (("pallas", ccfg),
                      ("scan", dataclasses.replace(ccfg, backend="scan"))):
        n_mods = len(ir_modules("jit_fused"))
        tc.reset_dispatch_counts()
        clock.take()
        t0 = time.perf_counter()
        res = tc.run_curves(cfg, n_devices=1)    # results fetched to host
        wall = time.perf_counter() - t0
        runs[name] = res
        log("train", backend=name, wall_s=wall, compile_s=clock.take(),
            dispatches=tc.dispatch_counts()["fused"], acc=res.acc.tolist(),
            first_loss=res.loss_history[0, 0].tolist(),
            final_loss=res.loss_history[0, -1].tolist())
        check(tc.dispatch_counts()["fused"] == len(cfg.bits),
              f"train/{name}: one fused dispatch per bits value")
        if name == "pallas" and expect_kernel:
            check_kernel(ir_modules("jit_fused")[n_mods:],
                         "train: the fused curve program")
    p = runs["pallas"]
    check(all(np.all(np.isfinite(x)) for x in
              (p.loss_history, p.acc, p.nll)), "train: finite losses")
    check(bool(np.all(p.loss_history[0, -1] < p.loss_history[0, 0])),
          "train: final logged loss below the first, every lane")
    diffs = {f: float(np.max(np.abs(getattr(p, f) - getattr(runs["scan"],
                                                              f))))
             for f in ("acc", "nll", "loss_history")}
    log("train", pallas_vs_scan_max_abs_diff=diffs, tol=TRAIN_TOL)
    check(max(diffs.values()) <= TRAIN_TOL,
          f"train: pallas and scan curves agree within {TRAIN_TOL}")


# ---------------------------------------------------------------------------
# phase serve
# ---------------------------------------------------------------------------

def check_aggregate(seed: int, n_workers: int, k: int, d_model: int):
    """Protocol.aggregate at the decode tick's shape: pallas == scan."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ocs
    from repro.protocol import Protocol

    def aggregate_pair(h, rng, p_miss):
        out = {}
        for backend in ("scan", "pallas"):
            proto = Protocol.ocs(bits=8, p_miss=p_miss, max_rounds=3,
                                 backend=backend)
            pooled, acct = proto.aggregate(h, rng)
            res = ocs.ocs_maxpool_noisy(h.reshape(n_workers, -1), rng,
                                        bits=8, p_miss=p_miss, max_rounds=3,
                                        backend=backend)
            out[backend] = dict(pooled=pooled, winner=res.winner,
                                rounds=acct.rounds,
                                slots=acct.contention_slots,
                                collisions=acct.collisions,
                                correct_frac=acct.correct_frac)
        return out

    key_h, key_c = jax.random.split(jax.random.PRNGKey(seed + 1))
    h = jax.random.normal(key_h, (n_workers, k // d_model, d_model),
                          jnp.bfloat16)
    p_miss = jnp.full((n_workers,), 0.05, jnp.float32)
    out = jax.device_get(jax.jit(aggregate_pair)(h, key_c, p_miss))
    for f in out["scan"]:
        a, b = np.asarray(out["scan"][f]), np.asarray(out["pallas"][f])
        same = a.dtype == b.dtype and np.array_equal(a, b)
        if not same and a.shape == b.shape:
            log("serve", field=f, differing=int(np.sum(a != b)))
        check(same, f"aggregate/{f}: pallas == scan bit for bit at "
                    f"N={n_workers}, K={k}")
    log("serve", aggregate_rounds=int(out["pallas"]["rounds"]),
        aggregate_slots=int(out["pallas"]["slots"]),
        aggregate_collisions=int(out["pallas"]["collisions"]))


def phase_serve(seed: int, clock: CompileClock, argv=None):
    import numpy as np

    from repro.launch import serve
    from repro.serve import engine as se

    args = serve.parse_args(argv or [
        "--arch", "qwen1.5-0.5b", "--batch-slots", "8", "--max-seq", "256",
        "--eos-id", "-1", "--p-miss", "0.05", "--bits", "8",
        "--max-rounds", "3", "--backend", "pallas", "--requests", "16",
        "--rate", "1.0", "--prompt-len", "64", "--max-new", "32",
        "--seed", str(seed)])
    clock.take()
    t0 = time.perf_counter()
    m, engine, reqs = serve.build(args)
    cfg = m.cfg
    log("serve", arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab_size, n_workers=cfg.n_workers,
        dtype=np.dtype(cfg.dtype).name, build_s=time.perf_counter() - t0)

    se.reset_trace_counts()
    for run in ("first", "second"):
        se.reset_dispatch_counts()
        clock.take()
        t0 = time.perf_counter()
        outs = engine.run(reqs)          # every token fetched to the host
        wall = time.perf_counter() - t0
        ticks = se.dispatch_counts()["tick"]
        log("serve", run=run, wall_s=wall, compile_s=clock.take(),
            ticks=ticks, s_per_tick=wall / ticks,
            tokens=sum(len(c.tokens) for c in outs.values()))
    check(sorted(outs) == sorted(r.rid for r in reqs)
          and all(len(c.tokens) == args.max_new for c in outs.values()),
          f"serve: all {len(reqs)} requests complete with {args.max_new} "
          "tokens")
    check(se.trace_counts()["tick"] == 1,
          "serve: the channel tick compiled once for both runs")
    slots = [c.channel_slots for c in outs.values()]
    check(min(slots) > 0, f"serve: channel_slots > 0 (min {min(slots)})")
    proto = engine.config.protocol
    per_tok = (proto.comm_load(cfg.n_workers, cfg.d_model).uplink_bits
               * m.channel_sites())
    want = {rid: (len(c.tokens) - 1) * per_tok for rid, c in outs.items()}
    got = {rid: c.uplink_bits for rid, c in outs.items()}
    log("serve", uplink_bits_total=sum(got.values()),
        analytic_total=sum(want.values()))
    check(got == want, "serve: uplink_bits equal the analytic bill")

    clock.take()
    free = engine.run(reqs, protocol=None)
    ref = se.reference_tokens(m, engine.values, reqs, args.max_seq,
                              eos_id=args.eos_id)
    first_ok = all(free[r].tokens[0] == ref[r][0] for r in ref)
    n_tok = sum(len(t) for t in ref.values())
    n_same = sum(a == b for r in ref for a, b in zip(free[r].tokens,
                                                     ref[r]))
    log("serve", channel_free_compile_s=clock.take(),
        tokens_matching_reference=f"{n_same}/{n_tok}",
        share=n_same / n_tok)
    check(first_ok, "serve: channel-free first token of every request "
                    "matches the batch-1 prefill reference")
    check_aggregate(seed, cfg.n_workers, args.batch_slots * cfg.d_model,
                    cfg.d_model)


# ---------------------------------------------------------------------------
# phase four_chips
# ---------------------------------------------------------------------------

def phase_four_chips(seed: int, clock: CompileClock, *, steps: int = 20,
                     batch: int = 256, widths=None):
    import jax
    import numpy as np

    from repro.optim.compressed_allreduce import CompressedAllReduce
    from repro.sim import train_curves as tc

    check(len(jax.devices()) >= 4, f"four_chips: 4 devices "
                                   f"(found {len(jax.devices())})")
    ccfg = tc.CurveConfig(bits=(8,), p_miss=(0.0, 0.05), steps=steps,
                          batch=batch, backend="pallas", dp_shards=4,
                          seed=seed, **(widths or TRAIN_WIDTHS))
    compress = CompressedAllReduce.topk(1 / 8)
    runs = {}
    for n_dev in (4, 1):
        n_mods = len(ir_modules("jit_fused"))
        clock.take()
        t0 = time.perf_counter()
        res = tc.run_curves_dp(ccfg, compress, n_devices=n_dev)
        wall = time.perf_counter() - t0
        runs[n_dev] = res
        devs = {len(leaf.sharding.device_set)
                for leaf in jax.tree.leaves(res.params[0])}
        mods = ir_modules("jit_fused")[n_mods:]
        log("four_chips", n_devices=n_dev, wall_s=wall,
            compile_s=clock.take(), output_devices=sorted(devs),
            acc=res.acc.tolist(),
            dp_payload_bits_total=res.dp_payload_bits_total.tolist())
        check(devs == {n_dev}, f"four_chips: n_devices={n_dev} outputs "
                               f"span {n_dev} device(s)")
        if n_dev == 4:
            check(any("mhlo.num_partitions = 4" in t and "all_gather" in t
                      for t in mods),
                  "four_chips: the 4-device program is partitioned 4 ways "
                  "and holds the all-gather")
    a, b = runs[4], runs[1]
    check(np.array_equal(a.dp_payload_bits, b.dp_payload_bits)
          and np.array_equal(a.dp_payload_bits_total,
                             b.dp_payload_bits_total),
          "four_chips: dp_payload_bits equal exactly")
    diffs = {f: float(np.max(np.abs(getattr(a, f) - getattr(b, f))))
             for f in ("acc", "nll", "loss_history")}
    per_step = np.max(np.abs(a.loss_history - b.loss_history), axis=(0, 2))
    log("four_chips", four_vs_one_max_abs_diff=diffs,
        loss_diff_by_logged_step=dict(zip(a.logged_steps.tolist(),
                                          per_step.tolist())),
        step0_tol=DP_STEP0_TOL, tol=DP_TOL)
    check(per_step[0] <= DP_STEP0_TOL,
          f"four_chips: step-0 losses agree within {DP_STEP0_TOL}")
    check(max(diffs.values()) <= DP_TOL,
          f"four_chips: 4-device and 1-device curves agree within {DP_TOL}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    shutil.rmtree(IR_DIR, ignore_errors=True)

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    from repro.kernels import interpret_default

    jax.config.update("jax_dump_ir_to", str(IR_DIR))

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log("device", **device, jax=jax.__version__, cache=cache_dir)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    check(interpret_default() is False, "kernels compile (no interpreter)")
    clock = CompileClock(jax)

    if args.four_chips:
        phase_four_chips(args.seed, clock)
    else:
        phase_train(args.seed, clock)
        phase_serve(args.seed, clock)
        for name in ("jit__tick", "jit_aggregate_pair"):
            check_kernel(ir_modules(name), f"serve: {name}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except CheckFailed as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        sys.exit(1)

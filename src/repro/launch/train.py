"""Production training launcher.

Assembles (arch config x mesh x data x optimizer x trainer) from the CLI.
On the CPU container use ``--smoke`` (reduced config, tiny synthetic data);
on a real pod the same command line runs the full config against the
production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
      --steps 50 --fusion max --ckpt-dir /tmp/ck

Recommended XLA flags on real TPU (comm/compute overlap):
  --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true
  --xla_tpu_overlap_compute_collective_tc=true
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.data import pipeline
from repro.launch.cache import use_compile_cache
from repro.models import model as M
from repro.optim import optimizers, schedules
from repro.parallel import sharding as sh
from repro.train import trainer
from repro.train.trainer import TrainerConfig


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--fusion", default="max")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", type=float, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    get = get_reduced if args.smoke else get_config
    cfg = get(args.arch, tp_fusion=args.fusion)
    m = M.build(cfg)
    print(f"{cfg.name}: {cfg.param_count() / 1e6:.1f}M params, "
          f"fusion={cfg.tp_fusion}, devices={jax.device_count()}")

    values, _ = sh.split_tree(m.init(jax.random.PRNGKey(args.seed)))
    pcfg = pipeline.for_model(cfg, batch=args.batch, seq_len=args.seq,
                              seed=args.seed)
    opt = optimizers.adamw(
        schedules.for_arch(args.arch, args.lr, args.steps),
        weight_decay=0.01)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=max(args.steps // 4, 1), log_every=10,
                         microbatches=args.microbatches,
                         compress_k=args.compress)
    res = trainer.train(m.loss, values, opt,
                        lambda s: pipeline.batch_for_step(pcfg, s), tcfg)
    for row in res.history:
        print(f"step {row['step']:6d}  nll {row.get('nll', float('nan')):8.4f}"
              f"  lr {row.get('lr', 0):.2e}  {row['step_time_s']:.2f}s")
    if res.straggler_flags:
        print("straggler-flagged steps:", res.straggler_flags)


if __name__ == "__main__":
    main()

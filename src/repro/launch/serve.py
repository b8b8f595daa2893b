"""Serving launcher: checkpoint -> slot-batched decode loop, optionally
with the simulated wireless channel in every decode tick.

CLI flags map 1:1 onto :class:`repro.serve.engine.ServeConfig`
(``--batch-slots``/``--max-seq``/``--eos-id``/``--sample``/``--seed`` plus
the ``--p-miss``/``--bits``/... protocol fields and the
``--tick-us``/``--slot-us`` clock); the request stream comes from the
Poisson load generator (``--requests``/``--rate``/...).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --batch-slots 4 --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \
      --p-miss 0.05 --bits 8 --rate 0.5        # channel in the loop
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.checkpoint import checkpointer
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.launch.cache import use_compile_cache
from repro.models import model as M
from repro.parallel import sharding as sh
from repro.protocol import Protocol
from repro.serve.engine import ChannelClock, ServeConfig, ServeEngine
from repro.serve.load import near_far_protocol, poisson_requests


def _build_protocol(args, n_workers: int):
    if args.p_miss is None and not args.near_far:
        return None
    if args.near_far:
        return near_far_protocol(
            n_workers, bits=args.bits, p_near=args.p_miss or 0.0,
            p_far=args.p_far, max_rounds=args.max_rounds,
            backend=args.backend)
    p = np.full((n_workers,), args.p_miss, np.float32)
    return Protocol.ocs(bits=args.bits, p_miss=p,
                        max_rounds=args.max_rounds, backend=args.backend)


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    # ServeConfig fields, 1:1
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--sample", action="store_true",
                    help="categorical sampling instead of greedy argmax")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tick-us", type=float, default=50.0)
    ap.add_argument("--slot-us", type=float, default=1.0)
    # protocol fields (omit --p-miss/--near-far for channel-free serving)
    ap.add_argument("--p-miss", type=float, default=None,
                    help="carrier-sensing miss probability (all workers)")
    ap.add_argument("--near-far", action="store_true",
                    help="two-tier near/far p_miss mix (--p-miss=near tier)")
    ap.add_argument("--p-far", type=float, default=0.1)
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--max-rounds", type=int, default=3)
    ap.add_argument("--backend", default="scan", choices=("scan", "pallas"))
    # load generator
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrival rate (requests per decode tick)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    return ap.parse_args(argv)


def build(args):
    """The served stack of ``args``: (model, engine, request stream)."""
    get = get_reduced if args.smoke else get_config
    cfg = get(args.arch)
    m = M.build(cfg)
    values, _ = sh.split_tree(m.init(jax.random.PRNGKey(args.seed)))
    if args.ckpt_dir:
        restored, step, _ = checkpointer.restore(
            args.ckpt_dir, template={"values": values, "opt": None})
        values = restored["values"]
        print(f"restored checkpoint step {step}")

    clock = ChannelClock(tick_us=args.tick_us, slot_us=args.slot_us)
    config = ServeConfig(
        batch_slots=args.batch_slots, max_seq=args.max_seq,
        eos_id=args.eos_id, greedy=not args.sample,
        protocol=_build_protocol(args, cfg.n_workers), clock=clock,
        seed=args.seed)
    engine = ServeEngine(m, values, config)
    reqs = poisson_requests(args.requests, args.rate, cfg.vocab_size,
                            prompt_len=args.prompt_len,
                            max_new_tokens=args.max_new, seed=args.seed)
    return m, engine, reqs


def main(argv=None):
    use_compile_cache()
    args = parse_args(argv)
    _m, engine, reqs = build(args)
    clock = engine.config.clock
    outs = engine.run(reqs)
    for rid in sorted(outs):
        c = outs[rid]
        print(f"req {rid}: latency={c.latency_us(clock):.0f}us "
              f"({c.latency_ticks} ticks, {c.channel_slots} slots, "
              f"{c.uplink_bits} uplink bits) tokens={c.tokens}")


if __name__ == "__main__":
    main()

"""Channel-in-the-loop serving benchmark: tokens/sec and latency vs
channel quality.

Drives the slot-batched :class:`repro.serve.engine.ServeEngine` with a
Poisson request stream (``repro.serve.load``) and sweeps the channel from
error-free to degraded — every OCS point rebinds only the protocol's
traced ``p_miss`` leaf on ONE engine, so the whole quality sweep runs on a
single compiled decode tick.  Reported per channel point: tokens/sec
(generated tokens over wall clock) and p50/p99 end-to-end latency under
the :class:`~repro.serve.engine.ChannelClock` (compute ticks + measured
channel airtime).

Self-checks (RuntimeError on failure):

  * channel-free serving is bit-for-bit the plain decode loop: the
    engine's tokens equal a manual eager ``prefill``+``decode_step``
    reference, request by request,
  * one fused dispatch per decode tick (``dispatch_counts()["tick"]``
    equals the tick count of every run),
  * zero recompiles across channel quality: one trace serves every OCS
    ``p_miss`` point including the near/far mix,
  * the error-free OCS point decodes the same tokens as an ideal
    ``Protocol.ideal_max(bits)`` run (protocol at ``p_miss=0`` == ideal
    max, through the whole serving stack).

``--bench-json PATH`` (or ``bench_json_path=``) emits the numbers as
``BENCH_serve.json``; ``benchmarks/run.py`` writes the canonical copy at
the repo root on full (non ``--fast``) runs.

  PYTHONPATH=src python -m benchmarks.bench_serve           # full
  PYTHONPATH=src python -m benchmarks.bench_serve --smoke   # CI tier-1
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import List, Optional

import jax
import numpy as np

from repro import analysis
from repro.configs import get_reduced
from repro.models import model as M
from repro.parallel import sharding as sh
from repro.protocol import Protocol
from repro.serve import engine as se
from repro.serve.engine import ChannelClock, ServeConfig, ServeEngine
from repro.serve.load import near_far_protocol, poisson_requests


@dataclasses.dataclass(frozen=True)
class BenchConfig:
    d_model: int = 32
    d_ff: int = 64
    vocab_size: int = 64
    n_workers: int = 4
    n_requests: int = 24
    rate_per_tick: float = 2.0
    prompt_len: int = 6
    max_new_tokens: int = 8
    batch_slots: int = 4
    max_seq: int = 48
    bits: int = 8
    ocs_p_miss: tuple = (0.0, 0.05, 0.2)
    p_far: float = 0.2


def _smoke_config() -> BenchConfig:
    return BenchConfig()


def _full_config() -> BenchConfig:
    return BenchConfig(d_model=64, vocab_size=128, n_requests=200,
                       rate_per_tick=1.5, prompt_len=8, max_new_tokens=16,
                       batch_slots=8, max_seq=64,
                       ocs_p_miss=(0.0, 0.02, 0.05, 0.1, 0.2))


def _build(bc: BenchConfig):
    cfg = get_reduced("qwen1.5-0.5b", n_layers=2, d_model=bc.d_model,
                      n_heads=2, n_kv_heads=2, d_ff=bc.d_ff,
                      vocab_size=bc.vocab_size, n_workers=bc.n_workers)
    m = M.build(cfg)
    values, _ = sh.split_tree(m.init(jax.random.PRNGKey(0)))
    return cfg, m, values


def _serve_point(engine: ServeEngine, requests, protocol, clock):
    """One channel point: run to completion, return (outs, stats-dict)."""
    se.reset_dispatch_counts()
    t0 = time.perf_counter()
    outs = engine.run(requests, protocol=protocol)
    wall = time.perf_counter() - t0
    ticks = se.dispatch_counts()["tick"]
    gen_tokens = sum(len(c.tokens) for c in outs.values())
    lat_us = np.array([c.latency_us(clock) for c in outs.values()])
    slots = sum(c.channel_slots for c in outs.values())
    bits = sum(c.uplink_bits for c in outs.values())
    return outs, {
        "wall_s": wall,
        "ticks": ticks,
        "tokens": gen_tokens,
        "tokens_per_sec": gen_tokens / wall,
        "p50_latency_us": float(np.percentile(lat_us, 50)),
        "p99_latency_us": float(np.percentile(lat_us, 99)),
        "channel_slots": int(slots),
        "uplink_bits": int(bits),
    }


def _check_dispatch(name: str, outs, stats: dict, batch_slots: int) -> None:
    """One fused dispatch per decode tick — the shared ``repro.analysis``
    bracket: every dispatch decodes >=1 active slot (the engine never
    dispatches an empty batch) and <= batch_slots tokens."""
    decode_tokens = sum(len(c.tokens) - 1 for c in outs.values())
    analysis.assert_tick_dispatch_bracket(name, decode_tokens,
                                          stats["ticks"], batch_slots)


def run(smoke: bool = False,
        bench_json_path: Optional[str] = None) -> List[str]:
    bc = _smoke_config() if smoke else _full_config()
    cfg, m, values = _build(bc)
    clock = ChannelClock(tick_us=50.0, slot_us=1.0)
    config = ServeConfig(batch_slots=bc.batch_slots, max_seq=bc.max_seq,
                         eos_id=-1, greedy=True, clock=clock, seed=0)
    engine = ServeEngine(m, values, config)
    requests = poisson_requests(bc.n_requests, bc.rate_per_tick,
                                bc.vocab_size, prompt_len=bc.prompt_len,
                                max_new_tokens=bc.max_new_tokens, seed=0)
    n_workers = cfg.n_workers
    sites = m.channel_sites()

    rows: List[str] = []
    points = {}

    # warm the channel-free tick so timed points measure the engine, not
    # one-off compiles (the channel tick warms inside the traced-sweep
    # check below — its first point doubles as the warmup)
    warm = requests[:min(4, len(requests))]
    engine.run(warm, protocol=None)

    # -- channel-free baseline + bitwise reference check -------------------
    free_outs, free_stats = _serve_point(engine, requests, None, clock)
    _check_dispatch("free", free_outs, free_stats, bc.batch_slots)
    ref = se.reference_tokens(m, values, requests, bc.max_seq, eos_id=-1)
    for rid, toks in ref.items():
        if free_outs[rid].tokens != toks:
            raise RuntimeError(
                f"channel-free serving diverged from the plain decode loop "
                f"for request {rid}: {free_outs[rid].tokens} != {toks}")
    if any(c.channel_slots or c.uplink_bits for c in free_outs.values()):
        raise RuntimeError(
            "channel-free serving billed channel airtime/uplink bits")
    points["free"] = free_stats

    # -- OCS quality sweep: one compiled tick across every p_miss ----------
    # ONE compile serves the whole sweep (warm + every p_miss point + the
    # near/far mix): only the traced p_miss leaf changes between runs
    se.reset_trace_counts()
    engine.run(warm, protocol=Protocol.ocs(
        bits=bc.bits, p_miss=np.zeros((n_workers,), np.float32)))
    ocs_outs = {}
    for p in bc.ocs_p_miss:
        proto = Protocol.ocs(
            bits=bc.bits,
            p_miss=np.full((n_workers,), p, np.float32))
        name = f"ocs_p{p:g}"
        ocs_outs[p], stats = _serve_point(engine, requests, proto, clock)
        _check_dispatch(name, ocs_outs[p], stats, bc.batch_slots)
        points[name] = stats
    nf = near_far_protocol(n_workers, bits=bc.bits, p_near=0.0,
                           p_far=bc.p_far)
    nf_outs, nf_stats = _serve_point(engine, requests, nf, clock)
    _check_dispatch("near_far", nf_outs, nf_stats, bc.batch_slots)
    points[f"near_far_p{bc.p_far:g}"] = nf_stats
    traces = se.trace_counts()["tick"]
    if traces != 1:
        raise RuntimeError(
            f"channel sweep recompiled: {traces} traces across "
            f"{len(bc.ocs_p_miss) + 1} p_miss points — the protocol must "
            "enter the tick as a traced pytree leaf")

    # -- error-free OCS == ideal max through the whole serving stack -------
    assert bc.ocs_p_miss[0] == 0.0
    ideal = Protocol.ideal_max(bc.bits, tie_break="first")
    ideal_outs, _ = _serve_point(engine, requests, ideal, clock)
    for rid in ideal_outs:
        if ocs_outs[0.0][rid].tokens != ideal_outs[rid].tokens:
            raise RuntimeError(
                f"OCS p_miss=0 decoded different tokens than ideal max for "
                f"request {rid} — the protocol-outcome pooling must be "
                "bit-for-bit ideal when nothing is missed")

    # analytic uplink bill: comm_load per aggregate x sites x decoded tokens
    p0 = Protocol.ocs(bits=bc.bits,
                      p_miss=np.zeros((n_workers,), np.float32))
    per_tok = p0.comm_load(n_workers, cfg.d_model).uplink_bits * sites
    want = sum((len(c.tokens) - 1) * per_tok
               for c in ocs_outs[0.0].values())
    got = sum(c.uplink_bits for c in ocs_outs[0.0].values())
    if got != want:
        raise RuntimeError(
            f"uplink accounting off: billed {got} bits, analytic {want}")

    for name, s in points.items():
        rows.append(
            f"serve/{name},{s['wall_s'] / max(s['ticks'], 1) * 1e6:.0f},"
            f"tokens_per_sec={s['tokens_per_sec']:.1f};"
            f"p50_latency_us={s['p50_latency_us']:.0f};"
            f"p99_latency_us={s['p99_latency_us']:.0f};"
            f"ticks={s['ticks']};channel_slots={s['channel_slots']};"
            f"uplink_bits={s['uplink_bits']}")
    rows.append(
        f"serve/meta,0,requests={bc.n_requests};slots={bc.batch_slots};"
        f"points={len(points)};traces={traces};"
        f"free_bitwise_plain_decode=1;p0_matches_ideal=1")

    if bench_json_path:
        bench = {
            "bench": "serve",
            "smoke": smoke,
            "load": {"n_requests": bc.n_requests,
                     "rate_per_tick": bc.rate_per_tick,
                     "prompt_len": bc.prompt_len,
                     "max_new_tokens": bc.max_new_tokens},
            "engine": {"batch_slots": bc.batch_slots,
                       "max_seq": bc.max_seq,
                       "d_model": bc.d_model,
                       "n_workers": n_workers,
                       "channel_sites": sites,
                       "tick_us": clock.tick_us,
                       "slot_us": clock.slot_us},
            "points": {k: {kk: (round(vv, 3) if isinstance(vv, float)
                               else vv) for kk, vv in v.items()}
                       for k, v in points.items()},
            "traces_across_sweep": traces,
            "free_bitwise_plain_decode": True,
            "p0_matches_ideal": True,
        }
        with open(bench_json_path, "w") as f:
            json.dump(bench, f, indent=2, sort_keys=True)
            f.write("\n")
    return rows


if __name__ == "__main__":
    argv = sys.argv[1:]
    bench_json = None
    if "--bench-json" in argv:
        i = argv.index("--bench-json")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            sys.exit("usage: bench_serve [--smoke] [--bench-json PATH]")
        bench_json = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    for r in run(smoke="--smoke" in argv, bench_json_path=bench_json):
        print(r)

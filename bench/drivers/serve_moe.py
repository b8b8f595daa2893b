"""Driver of the slot-batched serving engine for a latent-attention MoE
decoder (the DeepSeek-V3 block: moonlight-16b-a3b).

The window opens on the standing queue in steady state.  Its first
``batch_slots`` requests are already in flight: each is drawn from the
queue's stationary state, with every token of the traffic's output-length
multiset equally likely to be the one in progress (a length-biased output
length and an age uniform within it).  A request so drawn has served its
age already: its prompt carries that many further tokens (its age rounded
down to one of ``DEPTHS`` depths, so the prefill compiles once per depth),
and it has the rest of its output still to serve.  Admissions and
retirements thus interleave at their steady rate from the first tick, and
the cache holds contexts spread over the whole of ``max_seq``.  Filling
the slots and the first tick are the warm-up: they compile every program
the window runs (the prefill at each depth, the tick, each slot's cache
scatter), and the window opens at the second tick's dispatch.

The token timing, the end-to-end metrics and the reference are the serve
driver's (``bench/drivers/serve.py``, imported); this module builds the
model from the configuration's own keys (the latent attention's widths,
the leading dense layers, the routed and shared experts and the router),
samples the check across the depths the requests reached, and counts the
work: model FLOPs of the *active* parameters for ``serve.mfu``
(``bench/lib/counts_moe.py``), and, for the rooflines of the decode tick,
the slots each tick served, the sum of their contexts and, traced,
the intervals the tick programs ran in.

The configuration's reference models no channel, so a traffic that sets
``p_miss`` is refused.  Besides the control (the reference at float8
operands), :func:`calibrate` reads two planted faults in the program's
place: experts selected without the router's selection bias, and the
shared experts left out.
"""

from __future__ import annotations

import contextlib
import time
import traceback

import numpy as np

from bench.drivers import serve as S
from bench.lib import counts_moe as CM
from bench.lib import harness as H
from bench.lib import traffic as T
from bench.lib.trace import capture, find_xplane, load_xplane, reduce_trace

FAULTS = ("no_bias", "no_shared")         # as the reference names them
DEPTHS = 4                                # prompt depths of the in-flight


def model_config(config: dict):
    """The program's ``ModelConfig`` from the configuration file."""
    import jax.numpy as jnp

    from repro.configs import get_config

    router = (config["scoring_func"], config["topk_method"],
              config["n_group"], config["topk_group"],
              config["norm_topk_prob"])
    if router != ("sigmoid", "noaux_tc", 1, 1, True):
        raise ValueError(f"router {router} is not the one this driver "
                         f"builds (sigmoid, noaux_tc, one group, top-k "
                         f"weights renormalised)")
    dt = jnp.dtype(config["dtype"])
    return get_config(
        config["arch"], dtype=dt, param_dtype=dt,
        n_layers=config["n_layers"], d_model=config["d_model"],
        n_heads=config["n_heads"], n_kv_heads=config["n_heads"],
        d_ff=config["d_ff"], vocab_size=config["vocab_size"],
        n_workers=config["n_workers"],
        first_dense_layers=config["first_k_dense_replace"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_shared_expert=config["n_shared_experts"] > 0,
        moe_shared_d_ff=(config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        moe_score="sigmoid", moe_select_bias=True,
        moe_routed_scale=config["routed_scaling_factor"],
        rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"])


def build(cell: H.Cell, seed: int) -> S.Served:
    """The engine over the model's weights made from the seed (as
    ``serve.build``, with this configuration's model)."""
    import jax

    from repro.models import model as M
    from repro.parallel import sharding as sh
    from repro.serve.engine import ServeConfig, ServeEngine

    if cell.traffic.get("p_miss") is not None:
        raise ValueError("this configuration's reference has no channel")
    m = M.build(model_config(cell.config))
    values = S.make_values(cell, seed)
    want, _ = sh.split_tree(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: values)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise ValueError("the model's parameter layout is not the one the "
                         "configuration's reference makes")
    eng = cell.workload["engine"]
    engine = ServeEngine(m, values, ServeConfig(
        batch_slots=eng["batch_slots"], max_seq=eng["max_seq"],
        eos_id=eng["eos_id"], greedy=True, seed=eng["seed"]))
    return S.Served(engine=engine, engine_seed=eng["seed"], values=values)


def in_flight(traffic: dict, n: int):
    """``(age, rest)`` of ``n`` requests in flight in the stationary
    state: every token of the traffic's output-length multiset equally
    likely to be the one in progress (stratified, the same for every
    seed); ``age`` tokens served, ``rest`` (at least 1) still to come."""
    out = traffic["out_len"]
    lengths = np.sort(T.lognormal_lengths(out["block"], out["median"],
                                          out["sigma"], out["min"],
                                          out["max"]))
    ends = np.cumsum(lengths)
    token = ((np.arange(n) + 0.5) * ends[-1] / n).astype(np.int64)
    j = np.searchsorted(ends, token, side="right")
    age = token - (ends[j] - lengths[j])
    return age, lengths[j] - age


def queue(cell: H.Cell, seed: int):
    """The standing queue as the window finds it (module docstring): the
    traffic's backlog, its first ``batch_slots`` requests in flight."""
    tr, vocab = cell.traffic, cell.config["vocab_size"]
    items = T.serve_backlog(tr, vocab, seed)
    b = cell.workload["engine"]["batch_slots"]
    age, rest = in_flight(tr, b)
    grid = tr["out_len"]["max"] // DEPTHS
    rng = np.random.default_rng(T.derive_seed(seed, 5))
    first = []
    for (prompt, _), k in zip(items[:b], rng.permutation(b)):
        served = rng.integers(0, vocab, grid * (age[k] // grid))
        first.append((np.concatenate([prompt, served.astype(np.int32)]),
                      int(rest[k])))
    return S.requests(first + items[b:])


def window(served: S.Served, cell: H.Cell, seed: int, seconds: float,
           spans: H.Spans, opened=None, tracing: bool = False):
    """Serve the standing queue and time ``seconds`` of it from the second
    tick's dispatch: ``(recorder, start, end)``.  ``opened`` is called as
    the window opens; with ``tracing`` the profiler records the window.

    The recorder's tick and admission spans stay on the host clock in
    ``spans``; only the window's span is written into a trace.  A tick
    runs thousands of device operations, and ``trace.reduce_trace``
    weighs every idle gap between two of them against every harness span
    in the window, which took minutes with a span per tick and
    admission."""
    eng = served.engine
    rec = S.Recorder(eng, spans)
    reqs = queue(cell, seed)
    rec.backlog = {r.rid: r for r in reqs}
    timed_tick, start = eng._tick, []
    with contextlib.ExitStack() as stack:
        def tick(*args):
            if not start and rec.ticks:
                if opened is not None:
                    opened()
                if tracing:
                    stack.enter_context(capture(H.TRACE_DIR))
                stack.enter_context(H.Spans(tracing)("window"))
                start.append(time.perf_counter())
                rec.deadline = start[0] + seconds
            return timed_tick(*args)

        eng._tick = tick
        try:
            eng.run(reqs)
        except S.WindowClosed:
            pass
        finally:
            rec.restore()
        t_end = time.perf_counter()
    return rec, start[0], t_end


def sample(rec: S.Recorder, seed: int, target: int):
    """Finished requests drawn from the seed, across the depths they
    reached: the finished split by the context they ended at into
    ``DEPTHS`` groups, one drawn from each in turn, the deepest first,
    until their served tokens reach ``target``."""
    outs = rec.engine.outputs
    done = sorted((rid for rid, c in outs.items()
                   if len(c.tokens) == rec.backlog[rid].max_new_tokens),
                  key=lambda r: (-outs[r].prompt_len - len(outs[r].tokens),
                                 r))
    rng = np.random.default_rng(T.derive_seed(seed, 4))
    groups = [list(rng.permutation(g)) for g in
              np.array_split(np.asarray(done, np.int64), DEPTHS) if g.size]
    picked, n = [], 0
    while n < target and any(groups):
        for g in groups:
            if g and n < target:
                picked.append(int(g.pop()))
                n += len(outs[picked[-1]].tokens)
    return picked


def logit_gaps(cell: H.Cell, served: S.Served, rec: S.Recorder, rids,
               precision=None) -> np.ndarray:
    """As ``serve.logit_gaps``, each prompt at its own length."""
    import jax

    base = cell.workload["check"].get("reference_precision", "highest")
    fwd = S.reference_forward(cell, served.engine_seed, base,
                              precision or base)
    t_pad = cell.traffic["prompt_len"] + cell.traffic["out_len"]["max"]
    zeros = np.zeros((t_pad,), np.int32)
    gaps = []
    for rid in rids:
        prompt, out = rec.backlog[rid].prompt, rec.engine.outputs[rid].tokens
        p, n = len(prompt), len(out)
        tokens, targets = zeros.copy(), zeros.copy()
        tokens[:p] = prompt
        tokens[p:p + n - 1] = out[:-1]
        targets[p - 1:p - 1 + n] = out
        gap = fwd(served.values, tokens, targets, zeros, zeros != 0, 0)
        gaps.append(np.asarray(jax.device_get(gap), np.float64)[
            p - 1:p - 1 + n])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def check(cell: H.Cell, served: S.Served, rec: S.Recorder, seed: int):
    """The gap statistics that have a limit in the workload file, and the
    uplink bill of every request (0 without a channel)."""
    spec = cell.workload["check"]
    rids = sample(rec, seed, spec["tokens"])
    t = time.perf_counter()
    stats = S.gap_stats(logit_gaps(cell, served, rec, rids))
    stats["uplink_bill_mismatch"] = float(sum(
        c.uplink_bits != 0 for c in rec.engine.outputs.values()))
    outs = rec.engine.outputs
    H.log(f"check: reference {time.perf_counter() - t:.3f} s, "
          f"{len(rids)} requests ending at contexts "
          f"{sorted(outs[r].prompt_len + len(outs[r].tokens) for r in rids)}"
          f", {spec['tokens']}+ served tokens, {stats}")
    return {name: H.Check(stats[name], limit)
            for name, limit in spec["limits"].items()}


def run(cell: H.Cell, *, seed: int, seconds: float, tracing: bool,
        t0: float, clock: H.CompileClock, device: dict) -> H.Run:
    """One run: set-up is the build, the slots' fill and the first tick;
    then the window and the check (the end-to-end metrics as
    ``serve.run`` computes them)."""
    from repro.serve import engine as se

    spans = H.Spans()
    t_build = time.perf_counter()
    served = build(cell, seed)
    t_fill = time.perf_counter()
    at_open = {}

    def opened():
        at_open.update(setup=clock.take(), traces=se.trace_counts()["tick"])

    if tracing:
        seconds = min(seconds, cell.workload["trace_seconds"])
    rec, t_start, t_end = window(served, cell, seed, seconds, spans, opened,
                                 tracing)
    trace = load_xplane(find_xplane(H.TRACE_DIR)) if tracing else None
    summary = reduce_trace(trace) if tracing else None
    in_window = clock.take()
    retraced = se.trace_counts()["tick"] - at_open["traces"]
    H.log(f"setup: imports {t_build - t0:.3f} s, build {t_fill - t_build:.3f}"
          f" s, fill and first tick {t_start - t_fill:.3f} s; "
          f"{at_open['setup']}")
    device = dict(device, memory_peak_bytes=H.memory_peak_bytes())
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    times = rec.token_times()
    delivered = sum(t_start < t <= t_end for ts in times.values() for t in ts)
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if t_start < b <= t_end]
    stamps = [t for _, _, t in rec.ticks if t_start < t <= t_end]
    steps = np.diff(stamps) * 1e3 if len(stamps) > 1 else np.zeros(1)
    n_admit = sum(t_start < t1 <= t_end for _, _, _, t1 in rec.inserts)
    H.log(f"window: {len(stamps)} ticks, {n_admit} admissions, "
          f"{delivered} tokens, {len(gaps)} inter-token gaps, "
          f"tick to tick ms p10/p50/p90 "
          f"{np.percentile(steps, [10, 50, 90]).round(3).tolist()}, "
          f"tick retraces {retraced}, {in_window}")
    S.free_state(served)
    error = None
    try:
        checks = check(cell, served, rec, seed)
    except Exception:                       # the check itself failed
        error = traceback.format_exc()
        checks = {}
    layer = window_layer(cell, rec, t_start, t_end, spans, device)
    layer["compiles_in_window"] = in_window["compiles"] + retraced
    if trace is not None:
        layer["tick_programs"] = CM.tick_programs(trace)
    return H.Run(
        e2e={"serve_tokens_per_s": delivered / (t_end - t_start),
             "itl_p95_ms": 1e3 * H.quantile(gaps, 0.95) if gaps
             else float("inf"),
             "setup_s": t_start - t0},
        attempted=n_admit, failed=0, checks=checks, device=device,
        summary=summary, error=error, layer=layer)


def window_layer(cell: H.Cell, rec: S.Recorder, t_start: float,
                 t_end: float, spans: H.Spans, device: dict) -> dict:
    """What the per-layer readers need: the model FLOPs of the window's
    work (active parameters), the admission spans, and the mean slots
    served and context summed over them per decode tick in the window."""
    s = CM.shapes(cell.config)
    p_len = {rid: len(r.prompt) for rid, r in rec.backlog.items()}
    flops = 0
    decoded = {}
    active, contexts = [], []
    for _, slots, t in rec.ticks:
        ctx = []
        for _, rid in slots:
            decoded[rid] = decoded.get(rid, 0) + 1
            ctx.append(p_len[rid] + decoded[rid])
        if t_start < t <= t_end:
            flops += sum(CM.token_flops(s, c) for c in ctx)
            active.append(len(ctx))
            contexts.append(sum(ctx))
    prefill = {}
    for rid, _, _, t1 in rec.inserts:
        if t_start < t1 <= t_end:
            if p_len[rid] not in prefill:
                prefill[p_len[rid]] = CM.prefill_flops(s, p_len[rid])
            flops += prefill[p_len[rid]]
    admissions = [e - s0 for s0, e in spans.of("insert")
                  if t_start < e <= t_end]
    return {"window_s": t_end - t_start, "model_flops": flops,
            "admission_s": admissions, "device_kind": device["kind"],
            "tick_active": float(np.mean(active)) if active else 0.0,
            "tick_context_sum": float(np.mean(contexts)) if contexts
            else 0.0}


def calibrate(cell: H.Cell, seeds, seconds: float, control_seeds: int = 4):
    """Per seed, the check's numbers for the program and, on the first
    ``control_seeds`` seeds, for the control (the reference at float8
    operands) and each planted fault (the float32 reference with the
    fault), each in the program's place, on one engine whose weights and
    traffic change with the seed."""
    served = build(cell, seeds[0])
    eng = served.engine
    for n, seed in enumerate(seeds):
        # one set of weights and one cache on the device at a time
        served.values = eng.values = None
        served.values = eng.values = S.make_values(cell, seed)
        if eng.cache is None:
            eng.cache = eng.m.cache_init(eng.B, eng.max_seq)
        rec, t_start, t_end = window(served, cell, seed, seconds, H.Spans())
        S.free_state(served)
        rids = sample(rec, seed, cell.workload["check"]["tokens"])
        t = time.perf_counter()
        prog = logit_gaps(cell, served, rec, rids)
        row = {"seed": seed, "window_s": t_end - t_start,
               "reference_s": time.perf_counter() - t,
               "requests": len(rids), "tokens": int(prog.size),
               "program": S.gap_stats(prog)}
        if n < control_seeds:
            for name in ("fp8",) + FAULTS:
                gaps = logit_gaps(cell, served, rec, rids, name)
                row["control" if name == "fp8" else name] = S.gap_stats(gaps)
        yield row

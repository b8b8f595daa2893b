"""Driver of the slot-batched serving engine (``repro.serve.engine``).

Set-up makes the model's weights from the seed on the device, builds the
engine (with the channel in every FFN of the decode tick where the traffic
sets a ``p_miss``), and warms it on a short run that fills every slot, so
every program the window drives is compiled.  The window serves a standing
backlog through ``ServeEngine.run``.  Harness wrappers around the engine's
``_tick`` and ``_insert`` time every token at the sync the loop already
makes (the tick's tokens are stamped when the loop reads them to the
host; the wrappers add no sync of their own) and close the window at its
deadline (the wrapped tick raises).

``serve_tokens_per_s`` counts the tokens delivered to the host in the
window over its whole time; ``itl_p95_ms`` is the 95th percentile of every
gap between consecutive tokens of one request that ended in the window.

The check samples finished requests from the seed, the longest among them,
and runs the plain reference once over each prompt with its served tokens,
on the same sensing stream: every served token's logit must lie close
below the reference's best at its position.  Every request's uplink bill
must equal the analytic bill.
"""

from __future__ import annotations

import dataclasses
import json
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench.lib import harness as H
from bench.lib import traffic as T
from bench.lib.trace import capture, find_xplane, load_xplane, reduce_trace

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "qkv_bias", "rope_theta", "norm_eps",
              "act", "norm", "tie_embeddings", "n_workers")


class WindowClosed(Exception):
    """Raised by the wrapped tick once the window's deadline has passed."""


def model_config(config: dict):
    """The program's ``ModelConfig``, every size from the config file."""
    import jax.numpy as jnp

    from repro.configs import get_config

    dt = jnp.dtype(config["dtype"])
    return get_config(config["arch"], dtype=dt, param_dtype=dt,
                      **{k: config[k] for k in MODEL_KEYS})


def protocol(cell: H.Cell):
    from repro.protocol import Protocol

    p = cell.traffic.get("p_miss")
    if p is None:
        return None
    ch = cell.config["channel"]
    return Protocol.ocs(bits=ch["bits"],
                        p_miss=np.full((cell.config["n_workers"],), p,
                                       np.float32),
                        max_rounds=ch["max_rounds"], backend=ch["backend"])


@dataclasses.dataclass
class Served:
    engine: object
    engine_seed: int
    values: object


def make_values(cell: H.Cell, seed: int):
    """The model's weights from the seed, on the device, in one call."""
    import jax

    key = jax.random.PRNGKey(T.derive_seed(seed, 0))
    return jax.jit(lambda k: cell.reference().init_params(cell.config,
                                                          k))(key)


def build(cell: H.Cell, seed: int) -> Served:
    import jax

    from repro.models import model as M
    from repro.parallel import sharding as sh
    from repro.serve.engine import ServeConfig, ServeEngine

    m = M.build(model_config(cell.config))
    values = make_values(cell, seed)
    want, _ = sh.split_tree(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: values)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise ValueError("the model's parameter layout is not the one the "
                         "configuration's reference makes")
    eng = cell.workload["engine"]
    # the engine bakes its sensing seed into the compiled tick: a fixed
    # seed keeps every run's programs in the compile cache
    engine_seed = eng["seed"]
    engine = ServeEngine(m, values, ServeConfig(
        batch_slots=eng["batch_slots"], max_seq=eng["max_seq"],
        eos_id=eng["eos_id"], greedy=True, protocol=protocol(cell),
        seed=engine_seed))
    return Served(engine=engine, engine_seed=engine_seed, values=values)


def requests(items, first_rid: int = 0):
    from repro.serve.engine import Request

    return [Request(rid=first_rid + i, prompt=prompt, max_new_tokens=n,
                    arrival_tick=0) for i, (prompt, n) in enumerate(items)]


def warm_up(served: Served, cell: H.Cell, seed: int) -> None:
    """A short run that fills every slot twice: compiles the prefill, the
    tick, and every slot's cache scatter and bookkeeping."""
    b = cell.workload["engine"]["batch_slots"]
    items = T.serve_backlog(dict(cell.traffic, backlog=2 * b),
                            cell.config["vocab_size"], seed + 1)
    served.engine.run(requests([(p, 2 + i % 5)
                                for i, (p, _) in enumerate(items)]))


class Delivered:
    """A tick's token array as the engine's loop receives it: the first
    time the loop reads it to the host (its own sync), the time is stamped
    on the tick's record."""

    __slots__ = ("array", "record")

    def __init__(self, array, record: list):
        self.array = array
        self.record = record

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self.array, dtype)
        if self.record[2] is None:
            self.record[2] = time.perf_counter()
        return out

    def __getitem__(self, index):
        return self.array[index]

    def __getattr__(self, name):
        return getattr(self.array, name)


class Recorder:
    """Wraps the engine's tick and admission: times every token, keeps the
    tick and slot of every decoded position, closes the window.

    A tick's tokens are stamped when the loop first reads them to the
    host; should the loop never read them that way, at the next call into
    the tick or the admission, which comes after it has used them."""

    def __init__(self, engine, spans: H.Spans):
        self.engine = engine
        self.deadline = float("inf")
        # [tick, [(slot, rid)], host time of delivery]
        self.ticks: List[list] = []
        self.inserts: List[Tuple[int, int, float, float]] = []
        tick, insert = engine._tick, engine._insert

        def timed_tick(*args):
            now = time.perf_counter()
            self.stamp_pending(now)
            if now >= self.deadline:
                raise WindowClosed
            active = [(s, engine.slot_req[s].rid)
                      for s in range(engine.B) if engine.active[s]]
            with spans("tick"):
                out = tick(*args)
            record = [int(args[-1]), active, None]
            self.ticks.append(record)
            return (Delivered(out[0], record), *out[1:])

        def timed_insert(slot, req):
            t = time.perf_counter()
            self.stamp_pending(t)
            with spans("insert"):
                insert(slot, req)
            self.inserts.append((req.rid, slot, t, time.perf_counter()))

        self._orig = (tick, insert)
        engine._tick, engine._insert = timed_tick, timed_insert

    def stamp_pending(self, now: float) -> None:
        if self.ticks and self.ticks[-1][2] is None:
            self.ticks[-1][2] = now

    def restore(self) -> None:
        """Put the engine's own tick and admission back."""
        self.stamp_pending(time.perf_counter())
        self.engine._tick, self.engine._insert = self._orig

    def token_times(self) -> Dict[int, List[float]]:
        """Host time each request's tokens were delivered, in order."""
        times = {rid: [t1] for rid, _, _, t1 in self.inserts}
        for _, active, t in self.ticks:
            for _, rid in active:
                times[rid].append(t)
        return times

    def decode_ticks(self) -> Dict[int, Tuple[int, List[int]]]:
        """Each request's slot and the ticks that decoded its positions."""
        out = {rid: (slot, []) for rid, slot, _, _ in self.inserts}
        for tick, active, _ in self.ticks:
            for _, rid in active:
                out[rid][1].append(tick)
        return out


def window(served: Served, cell: H.Cell, seed: int, seconds: float,
           spans: H.Spans):
    """Serve the backlog until the deadline: ``(recorder, start, end)``."""
    rec = Recorder(served.engine, spans)
    backlog = requests(T.serve_backlog(cell.traffic,
                                       cell.config["vocab_size"], seed))
    with spans("window"):
        t_start = time.perf_counter()
        rec.deadline = t_start + seconds
        try:
            served.engine.run(backlog)
        except WindowClosed:
            pass
        finally:
            rec.restore()
        t_end = time.perf_counter()
    rec.backlog = {r.rid: r for r in backlog}
    return rec, t_start, t_end


def uplink_bits_per_token(config: dict, traffic: dict) -> int:
    """Analytic uplink of one channel-decoded token: per FFN site, K =
    d_model payloads of ``bits`` bits, (bits + id_bits) contention
    sub-slots and an 8-bit ACK per sub-frame (paper §I, §IV)."""
    if traffic.get("p_miss") is None:
        return 0
    bits = config["channel"]["bits"]
    idb = max(1, int(np.ceil(np.log2(max(config["n_workers"], 2)))))
    k = config["d_model"]
    return config["n_layers"] * k * (bits + (bits + idb) + 8)


def sample(rec: Recorder, seed: int, target: int) -> List[int]:
    """Finished requests drawn from the seed, the longest first, until
    their served tokens reach ``target``."""
    outs = rec.engine.outputs
    done = [rid for rid, c in outs.items()
            if len(c.tokens) == rec.backlog[rid].max_new_tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(outs[r].tokens), -r))
    rng = np.random.default_rng(T.derive_seed(seed, 4))
    order = [longest] + [r for r in rng.permutation(sorted(done))
                         if r != longest]
    picked, n = [], 0
    for rid in order:
        picked.append(int(rid))
        n += len(outs[rid].tokens)
        if n >= target:
            break
    return picked


_FORWARDS: Dict[tuple, Callable] = {}


def reference_forward(cell: H.Cell, engine_seed: int, base: str,
                      precision: str) -> Callable:
    """``(values, tokens, targets, ticks, on, slot) -> gaps``, jitted once
    per process for each configuration, traffic and pair of precisions:
    for every position, how far the target token's logit lies below the
    best in the reference at precision ``base``.  Where ``precision``
    differs from ``base`` (the control), the target is the token that
    ``precision`` puts first."""
    import jax
    import jax.numpy as jnp

    cfg, tr = cell.config, cell.traffic
    slots = cell.workload["engine"]["batch_slots"]
    key = (json.dumps(cfg, sort_keys=True), json.dumps(tr, sort_keys=True),
           slots, engine_seed, base, precision)
    if key in _FORWARDS:
        return _FORWARDS[key]
    ref = cell.reference()
    p_miss = tr.get("p_miss")

    def run(values, tokens, targets, ticks, on, slot):
        chan = None
        if p_miss is not None:
            chan = {"ticks": ticks, "on": on, "slot": slot, "slots": slots,
                    "engine_seed": engine_seed,
                    "p_miss": jnp.full((cfg["n_workers"],), p_miss,
                                       jnp.float32)}
        logits = ref.forward(cfg, values, tokens, chan, base)
        if precision != base:
            targets = jnp.argmax(ref.forward(cfg, values, tokens, chan,
                                             precision), -1)
        got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
        return jnp.max(logits, -1) - got

    _FORWARDS[key] = jax.jit(run)
    return _FORWARDS[key]


def logit_gaps(cell: H.Cell, served: Served, rec: Recorder,
               rids: List[int], precision: Optional[str] = None
               ) -> np.ndarray:
    """For every served token of the sampled requests: how far its logit
    lies below the reference's best at its position.  The reference
    computes at the check's ``reference_precision`` (default
    ``highest``).  With another ``precision`` (the control) the token is
    the one that precision puts first."""
    import jax

    tr = cell.traffic
    base = cell.workload["check"].get("reference_precision", "highest")
    fwd = reference_forward(cell, served.engine_seed, base,
                            precision or base)
    p_len = tr["prompt_len"]
    t_pad = p_len + tr["out_len"]["max"]
    ticks_of = rec.decode_ticks()
    gaps = []
    for rid in rids:
        served_toks = rec.engine.outputs[rid].tokens
        n = len(served_toks)
        slot, ticks = ticks_of[rid]
        tokens = np.zeros((t_pad,), np.int32)
        tokens[:p_len] = rec.backlog[rid].prompt
        tokens[p_len:p_len + n - 1] = served_toks[:-1]
        targets = np.zeros((t_pad,), np.int32)
        targets[p_len - 1:p_len - 1 + n] = served_toks
        pos_ticks = np.zeros((t_pad,), np.int32)
        pos_ticks[p_len:p_len + n - 1] = ticks[:n - 1]
        on = np.zeros((t_pad,), bool)
        on[p_len:p_len + n - 1] = True
        gap = np.asarray(jax.device_get(fwd(served.values, tokens, targets,
                                            pos_ticks, on, slot)),
                         np.float64)
        gaps.append(gap[p_len - 1:p_len - 1 + n])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    """The widest gap and the mean gap over the compared tokens."""
    if not gaps.size:
        return {"logit_gap": float("inf"), "logit_gap_mean": float("inf")}
    return {"logit_gap": float(np.max(gaps)),
            "logit_gap_mean": float(np.mean(gaps))}


def check(cell: H.Cell, served: Served, rec: Recorder, seed: int
          ) -> Dict[str, H.Check]:
    """The gap statistics that have a limit in the workload file, and the
    uplink bill of every request."""
    spec = cell.workload["check"]
    per_tok = uplink_bits_per_token(cell.config, cell.traffic)
    wrong = sum(c.uplink_bits != (len(c.tokens) - 1) * per_tok
                for c in rec.engine.outputs.values())
    rids = sample(rec, seed, spec["tokens"])
    t = time.perf_counter()
    stats = gap_stats(logit_gaps(cell, served, rec, rids))
    H.log(f"check: reference {time.perf_counter() - t:.3f} s")
    stats["uplink_bill_mismatch"] = float(wrong)
    H.log(f"check: {len(rids)} requests, {spec['tokens']}+ served tokens, "
          f"{stats}")
    return {name: H.Check(stats[name], limit)
            for name, limit in spec["limits"].items()}


def free_state(served: Served) -> None:
    """Drop the engine's KV cache before the reference runs."""
    eng = served.engine
    eng.cache = None
    eng.positions = eng.cur_token = None


def run(cell: H.Cell, *, seed: int, seconds: float, tracing: bool,
        t0: float, clock: H.CompileClock, device: dict) -> H.Run:
    from repro.serve import engine as se

    spans = H.Spans(tracing)
    t_build = time.perf_counter()
    served = build(cell, seed)
    t_warm = time.perf_counter()
    warm_up(served, cell, seed)
    setup = clock.take()
    H.log(f"setup: imports {t_build - t0:.3f} s, build {t_warm - t_build:.3f}"
          f" s, warm-up {time.perf_counter() - t_warm:.3f} s; {setup}")
    traces = se.trace_counts()["tick"]
    if tracing:
        seconds = min(seconds, cell.workload["trace_seconds"])
        with capture(H.TRACE_DIR):
            rec, t_start, t_end = window(served, cell, seed, seconds, spans)
        summary = reduce_trace(load_xplane(find_xplane(H.TRACE_DIR)))
    else:
        rec, t_start, t_end = window(served, cell, seed, seconds, spans)
        summary = None
    in_window = clock.take()
    retraced = se.trace_counts()["tick"] - traces
    device = dict(device, memory_peak_bytes=H.memory_peak_bytes())
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
    times = rec.token_times()
    delivered = sum(t_start < t <= t_end for ts in times.values() for t in ts)
    gaps = [b - a for ts in times.values() for a, b in zip(ts, ts[1:])
            if t_start < b <= t_end]
    stamps = [t for _, _, t in rec.ticks if t_start < t <= t_end]
    steps = np.diff(stamps) * 1e3 if len(stamps) > 1 else np.zeros(1)
    H.log(f"window: {len(rec.ticks)} ticks, {len(rec.inserts)} admissions, "
          f"{delivered} tokens, {len(gaps)} inter-token gaps, "
          f"tick to tick ms p10/p50/p90 "
          f"{np.percentile(steps, [10, 50, 90]).round(3).tolist()}, "
          f"tick retraces {retraced}, {in_window}")
    free_state(served)
    error = None
    try:
        checks = check(cell, served, rec, seed)
    except Exception:                       # the check itself failed
        error = traceback.format_exc()
        checks = {}
    elapsed = t_end - t_start
    layer = window_layer(cell, rec, t_start, t_end, spans, device)
    layer["compiles_in_window"] = in_window["compiles"] + retraced
    return H.Run(
        e2e={"serve_tokens_per_s": delivered / elapsed,
             "itl_p95_ms": 1e3 * H.quantile(gaps, 0.95) if gaps
             else float("inf"),
             "setup_s": t_start - t0},
        attempted=len(rec.inserts), failed=0, checks=checks, device=device,
        summary=summary, error=error, layer=layer)


def window_layer(cell: H.Cell, rec: Recorder, t_start: float, t_end: float,
                 spans: H.Spans, device: dict) -> dict:
    """What the per-layer readers need: model FLOPs of the window's work
    and the admission spans."""
    from bench.lib import counts as C

    cfg, tr = cell.config, cell.traffic
    model = {k: cfg[k] for k in ("n_layers", "d_model", "n_heads",
                                 "n_kv_heads", "head_dim", "d_ff",
                                 "vocab_size")}
    p_len = tr["prompt_len"]
    flops = 0
    decoded = {}
    for _, active, t in rec.ticks:
        for _, rid in active:
            decoded[rid] = decoded.get(rid, 0) + 1
            if t_start < t <= t_end:
                ctx = p_len + decoded[rid]
                flops += C.decoder_token_flops(ctx, **model)
    n_admit = sum(t_start < t1 <= t_end for _, _, _, t1 in rec.inserts)
    flops += n_admit * C.prefill_flops(p_len, **model)
    admissions = [e - s for s, e in spans.of("insert") if t_start < e <= t_end]
    out = {"window_s": t_end - t_start, "model_flops": flops,
           "admission_s": admissions, "device_kind": device["kind"]}
    if tr.get("p_miss") is not None:
        # one contention per FFN site of every tick, over every slot's
        # d_model sub-frames
        n, ch = cfg["n_workers"], cfg["channel"]
        out["kernel_work"] = [(cfg["n_layers"], C.contention_work(
            n, cell.workload["engine"]["batch_slots"] * cfg["d_model"],
            ch["bits"], C.id_bits(n), ch["max_rounds"]))]
        out["kernel_units"] = len(rec.ticks)
    return out


def calibrate(cell: H.Cell, seeds, seconds: float, control_seeds: int = 4):
    """Per seed, the check's numbers for the program and, on the first
    ``control_seeds`` seeds, for the control (the reference at float8
    operands in the program's place), on one engine whose weights and
    traffic change with the seed."""
    served = build(cell, seeds[0])
    warm_up(served, cell, seeds[0])
    for n, seed in enumerate(seeds):
        served.values = served.engine.values = make_values(cell, seed)
        rec, t_start, t_end = window(served, cell, seed, seconds, H.Spans())
        rids = sample(rec, seed, cell.workload["check"]["tokens"])
        t = time.perf_counter()
        prog = logit_gaps(cell, served, rec, rids)
        row = {"seed": seed, "window_s": t_end - t_start,
               "reference_s": time.perf_counter() - t,
               "requests": len(rids), "tokens": int(prog.size),
               "program": dict(gap_stats(prog),
                               mismatch_share=float(np.mean(prog > 0)))}
        if n < control_seeds:
            ctrl = logit_gaps(cell, served, rec, rids, "fp8")
            row["control"] = dict(gap_stats(ctrl),
                                  mismatch_share=float(np.mean(ctrl > 0)))
        yield row

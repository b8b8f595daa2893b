"""Channel-in-the-loop serving: slot-based continuous batching with the
wireless aggregation protocol inside the decode tick.

A fixed budget of B slots decodes in lock-step.  Each tick is ONE fused
jitted dispatch — decode through the stack (optionally aggregating every
mlp-FFN worker fusion through a simulated :class:`repro.protocol.Protocol`
channel), next-token selection (greedy argmax or categorical sampling) and
the position increment all live inside the same compiled program, and the
protocol rides in as a traced pytree argument so rebinding ``p_miss``
(e.g. sweeping channel quality) never recompiles.  Finished slots (EOS or
length cap) retire and refill from the arrival queue by running a
single-request prefill and scattering its KV cache into the batch cache at
the slot index — the standard continuous-batching structure, minus
speculative/paged refinements.

Airtime accounting: the contention core measures the channel slots each
tick actually consumed (``ProtocolAccounting`` summed over the stack's
:func:`repro.models.model.channel_sites`), and a :class:`ChannelClock`
converts ticks + slots to wall time, so every :class:`Completion` carries
its end-to-end latency decomposed into compute ticks vs channel slots.

Dispatch/trace counters mirror ``repro.sim.train_curves``:
``dispatch_counts()["tick"]`` counts host->device decode-tick dispatches
(exactly one per tick — self-checked by ``benchmarks/bench_serve.py``) and
``trace_counts()["tick"]`` counts compilations of the fused tick; both are
views of the :mod:`repro.obs` registry.

The host loop is spanned (:func:`repro.obs.span`): ``serve.admit`` around
each admission (``rid``, ``slot``), ``serve.tick`` from a tick's dispatch
to the end of its per-slot bookkeeping (``tick``), and ``serve.sync``
around every device->host read the loop makes (``what``: ``tokens``,
``airtime``, ``flags`` or ``first_token``).  The loop never reads a slot's
position back: the host derives it from the tokens it has delivered.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs
from repro.protocol import Protocol

_TRACE = "serve.trace."
_DISPATCH = "serve.dispatch."


def trace_counts() -> Dict[str, int]:
    return obs.view(_TRACE, ("tick",))


def dispatch_counts() -> Dict[str, int]:
    return obs.view(_DISPATCH, ("tick",))


def reset_trace_counts() -> None:
    obs.reset(_TRACE)


def reset_dispatch_counts() -> None:
    obs.reset(_DISPATCH)


def _sync(what: str, read: Callable[[], Any]) -> Any:
    """A device->host read of the serving loop, in a ``serve.sync`` span."""
    with obs.span("serve.sync", what=what):
        return read()


@dataclasses.dataclass(frozen=True)
class ChannelClock:
    """Converts the engine's discrete accounting to wall time.

    ``tick_us`` is the compute cost of one lock-step decode tick (the
    forward pass over all B slots); ``slot_us`` the airtime of one channel
    sub-slot (contention bit-slots and payload bits are both billed in
    ``ProtocolAccounting.contention_slots`` units by the contention core).
    """

    tick_us: float = 50.0
    slot_us: float = 1.0

    def __post_init__(self):
        if self.tick_us <= 0 or self.slot_us <= 0:
            raise ValueError("ChannelClock times must be positive")

    def latency_us(self, ticks: int, slots: int) -> float:
        return ticks * self.tick_us + slots * self.slot_us


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Typed serving surface (replaces ``ServeEngine``'s kwarg pile).

    ``protocol=None`` keeps serving channel-free (the zero-cost default:
    the decode tick runs the exact historical ops).  An OCS protocol must
    carry a bound ``p_miss``; per-run overrides go through
    ``ServeEngine.run(requests, protocol=...)`` which rebinds only the
    traced leaf, so a quality sweep never recompiles.
    """

    batch_slots: int = 4
    max_seq: int = 128
    eos_id: int = 1
    greedy: bool = True
    protocol: Optional[Protocol] = None
    fault: Optional[faults.FaultModel] = None
    clock: ChannelClock = dataclasses.field(default_factory=ChannelClock)
    seed: int = 0

    def __post_init__(self):
        if self.batch_slots < 1:
            raise ValueError("batch_slots must be >= 1")
        if self.max_seq < 2:
            raise ValueError("max_seq must be >= 2")
        if self.protocol is not None and self.protocol.kind == "concat":
            raise ValueError(
                "concat protocols cannot serve in-block fusion (the fused "
                "width N*K does not match the residual width K)")
        if self.fault is not None and self.protocol is None:
            raise ValueError(
                "fault injection needs a channel protocol (fault models "
                "perturb the sensing channel; channel-free serving has "
                "no channel to fault)")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 32
    arrival_tick: int = 0        # Poisson load generators set this


@dataclasses.dataclass
class Completion:
    """One served request, self-describing under the channel budget.

    ``latency_ticks`` spans arrival to retirement inclusive (queue wait
    included); ``channel_slots`` is the measured contention+payload airtime
    the shared channel consumed over that span; ``uplink_bits`` the
    analytic per-request uplink (``Protocol.comm_load`` per aggregate call
    x channel sites x channel-decoded tokens).  All three are 0 for
    channel-free serving.

    Under fault injection (``ServeConfig.fault``) two degradation counters
    ride along: ``degraded_tokens`` counts tokens this request emitted on
    outage ticks (every worker offline — the degrade policy substituted a
    filler instead of wedging the FIFO), and ``retry_ticks`` counts ticks
    the whole batch stalled re-contending under the ``retry`` policy.

    ``token_ticks`` holds, for each token, the engine tick that delivered
    it: the admitting tick for the first (the prefill's), then the decode
    tick of each further token.  With the ``serve.tick`` and
    ``serve.admit`` spans it gives each token's delivery time.
    """

    rid: int
    tokens: List[int]
    prompt_len: int
    latency_ticks: int = 0
    channel_slots: int = 0
    uplink_bits: int = 0
    degraded_tokens: int = 0
    retry_ticks: int = 0
    token_ticks: List[int] = dataclasses.field(default_factory=list)

    def latency_us(self, clock: ChannelClock) -> float:
        return clock.latency_us(self.latency_ticks, self.channel_slots)


_UNSET = object()


class ServeEngine:
    """Slot-batched serving engine over an optional simulated channel.

    One engine instance holds ONE compiled tick per protocol *structure*
    (channel-free, or one per protocol treedef); sweeping ``p_miss``
    through ``run(requests, protocol=...)`` reuses the compiled tick.
    """

    def __init__(self, model, values, config: ServeConfig):
        self.m = model
        self.values = values
        self.config = config
        self.B = config.batch_slots
        self.max_seq = config.max_seq
        self.eos = config.eos_id
        cfg = model.cfg
        self._sites = model.channel_sites()
        self._bits_per_site = {}      # protocol id -> analytic uplink bits
        self.cache = model.cache_init(self.B, self.max_seq)
        # each cache leaf's batch axis, as the model declares it
        self._batch_axes = jax.tree.unflatten(
            jax.tree.structure(self.cache),
            [axes.index("batch") for axes in jax.tree.leaves(
                model.cache_axes(), is_leaf=_is_axes)])
        self.positions = jnp.zeros((self.B,), jnp.int32)
        self.cur_token = jnp.zeros((self.B, 1), jnp.int32)
        self.active = np.zeros((self.B,), bool)
        self.budget = np.zeros((self.B,), np.int64)
        self.slot_req: List[Optional[Request]] = [None] * self.B
        self.outputs: Dict[int, Completion] = {}

        base_key = jax.random.PRNGKey(config.seed)
        sample_key = jax.random.fold_in(base_key, 0x5A)

        def _tick(v, protocol, fault, fstate, cur_token, positions, cache,
                  tick):
            obs.count(_TRACE + "tick")
            if protocol is None:
                logits, new_cache = model.decode_step(v, cur_token,
                                                      positions, cache)
                chan = None
            elif fault is None:
                rng = jax.random.fold_in(base_key, tick)
                logits, new_cache, chan = model.decode_step_channel(
                    v, cur_token, positions, cache, protocol, rng)
            else:
                # evolve the Gilbert-Elliott sensing chain + dropout spans
                # one step per tick, then rebind the protocol's traced
                # leaves -- fault parameters never recompile the tick
                rng = jax.random.fold_in(base_key, tick)
                new_bad, new_offline = faults.step_chains(fault, fstate, rng)
                online = ~new_offline
                proto_f = protocol.with_p_miss(
                    faults.effective_p_miss(fault, new_bad)
                ).with_online(online)
                logits, new_cache, chan = model.decode_step_channel(
                    v, cur_token, positions, cache, proto_f, rng)
            if config.greedy:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                nxt = jax.random.categorical(
                    jax.random.fold_in(sample_key, tick),
                    logits).astype(jnp.int32)
            if fault is None:
                return nxt, positions + 1, new_cache, chan, fstate, None
            # Degrade instead of wedging: on an outage tick (every worker
            # offline) the pooled fusions resolved nothing, so the decode
            # output is garbage -- the policy decides what the slots emit.
            ok = jnp.any(online)
            consec = jnp.where(ok, jnp.int32(0),
                               fstate.consec + jnp.int32(1))
            age = jnp.where(ok, jnp.int32(0), fstate.age + jnp.int32(1))
            kind = fault.policy.kind                     # static meta
            if kind == "retry":
                retrying = (~ok) & (
                    consec <= jnp.int32(fault.policy.retry_budget))
            else:
                retrying = jnp.bool_(False)
            if kind == "stale":
                deg_tok = cur_token[:, 0]     # repeat the last token
            else:                             # zero_fill / exhausted retry
                deg_tok = jnp.zeros_like(nxt)
            nxt = jnp.where(ok, nxt, deg_tok)
            # a retry tick makes no progress: token/positions/cache hold
            # while the chain re-contends (airtime still billed via chan)
            commit = ok | ~retrying
            nxt = jnp.where(commit, nxt, cur_token[:, 0])
            new_positions = jnp.where(commit, positions + 1, positions)
            new_cache = jax.tree.map(
                lambda nc, oc: jnp.where(commit, nc, oc), new_cache, cache)
            new_fstate = dataclasses.replace(
                fstate, bad=new_bad, offline=new_offline, age=age,
                consec=consec)
            flags = {"ok": ok, "retrying": retrying}
            return nxt, new_positions, new_cache, chan, new_fstate, flags

        self._tick = jax.jit(_tick)
        self._prefill = jax.jit(
            lambda v, b: model.prefill(v, b, max_seq=self.max_seq))
        self._d_model = cfg.d_model
        self._n_workers = cfg.n_workers

    # -- analytic uplink accounting ----------------------------------------

    def _uplink_bits_per_tick(self, protocol: Optional[Protocol]) -> int:
        """Per-slot analytic uplink bits of one channel-decoded token."""
        if protocol is None:
            return 0
        key = dataclasses.replace(protocol, p_miss=None)  # static meta only
        if key not in self._bits_per_site:
            load = protocol.comm_load(self._n_workers, self._d_model)
            self._bits_per_site[key] = load.uplink_bits * self._sites
        return self._bits_per_site[key]

    # -- slot management ----------------------------------------------------

    def _reset(self) -> None:
        """Clear slot state between runs (the cache is reused: a prefill
        scatter overwrites a slot's rows end to end before it activates)."""
        self.positions = jnp.zeros((self.B,), jnp.int32)
        self.cur_token = jnp.zeros((self.B, 1), jnp.int32)
        self.active[:] = False
        self.budget[:] = 0
        self.slot_req = [None] * self.B
        self.outputs = {}

    def _insert(self, slot: int, req: Request):
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, cache1 = self._prefill(self.values, {"tokens": tokens})
        # scatter the single-request cache into the batch cache at `slot`
        def put(batch_leaf, one_leaf, axis):
            idx = [slice(None)] * batch_leaf.ndim
            idx[axis] = slice(slot, slot + 1)
            return batch_leaf.at[tuple(idx)].set(
                one_leaf.astype(batch_leaf.dtype))

        self.cache = jax.tree.map(put, self.cache, cache1, self._batch_axes)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)[0]
        self.cur_token = self.cur_token.at[slot, 0].set(tok)
        self.positions = self.positions.at[slot].set(len(req.prompt))
        self.active[slot] = True
        self.budget[slot] = req.max_new_tokens - 1
        self.slot_req[slot] = req
        self.outputs[req.rid] = Completion(
            rid=req.rid, tokens=[_sync("first_token", lambda: int(tok))],
            prompt_len=len(req.prompt))

    def _retire(self, slot: int):
        self.active[slot] = False
        self.slot_req[slot] = None

    # -- main loop ----------------------------------------------------------

    def run(self, requests: List[Request], protocol=_UNSET, fault=_UNSET,
            stop: Optional[Callable[[], bool]] = None
            ) -> Dict[int, Completion]:
        """Serve ``requests`` to completion; returns ``{rid: Completion}``.

        Requests are admitted FIFO by ``arrival_tick`` (ties keep
        submission order); with no slot free and no arrival due, the tick
        counter fast-forwards to the next arrival instead of dispatching
        empty decode ticks.  ``protocol`` overrides the config's (pass
        ``None`` for an explicitly channel-free run) — only the traced
        ``p_miss`` leaf differs between runs of equal structure, so the
        compiled tick is reused.  ``fault`` likewise overrides
        ``config.fault`` (a ``repro.faults.FaultModel``): bursty sensing
        fades and worker outages then ride the decode tick, with outage
        ticks *degrading* completions per the model's policy instead of
        wedging the FIFO — every fault parameter is a traced leaf, so a
        fault sweep reuses the compiled tick too.

        ``stop`` (optional, no arguments) is asked before each admission
        round and before each tick; once it returns true the run ends and
        returns the completions as they stand, unfinished ones included.
        """
        proto = self.config.protocol if protocol is _UNSET else protocol
        fm = self.config.fault if fault is _UNSET else fault
        if fm is not None and proto is None:
            raise ValueError("fault injection needs a channel protocol")
        fstate = (faults.init_state(self._n_workers)
                  if fm is not None else None)
        bits_per_tok = self._uplink_bits_per_tick(proto)
        self._reset()
        pending = sorted(requests, key=lambda r: r.arrival_tick)
        admissible: List[Request] = []
        tick = 0
        total_slots = 0                       # cumulative measured airtime
        slots_at_arrival: Dict[int, int] = {}
        arrival_of: Dict[int, int] = {}
        while pending or admissible or self.active.any():
            while pending and pending[0].arrival_tick <= tick:
                r = pending.pop(0)
                admissible.append(r)
                slots_at_arrival[r.rid] = total_slots
                arrival_of[r.rid] = r.arrival_tick
            if not self.active.any() and not admissible:
                tick = pending[0].arrival_tick   # idle: jump to next arrival
                continue
            if stop is not None and stop():
                break
            for slot in range(self.B):
                if not self.active[slot] and admissible:
                    req = admissible.pop(0)
                    with obs.span("serve.admit", rid=req.rid, slot=slot):
                        self._insert(slot, req)
                    self.outputs[req.rid].token_ticks.append(tick)
            if stop is not None and stop():
                break
            with obs.span("serve.tick", tick=tick):
                obs.count(_DISPATCH + "tick")
                nxt, self.positions, self.cache, chan, fstate, flags = \
                    self._tick(self.values, proto, fm, fstate,
                               self.cur_token, self.positions, self.cache,
                               jnp.int32(tick))
                self.cur_token = nxt[:, None]
                tick += 1
                if chan is not None:
                    total_slots += _sync(
                        "airtime", lambda: int(chan["contention_slots"]))
                if flags is not None and _sync(
                        "flags", lambda: bool(flags["retrying"])):
                    # retry tick: the batch held position re-contending;
                    # bill the stall against every in-flight request and
                    # move on
                    for slot in range(self.B):
                        if self.active[slot]:
                            self.outputs[self.slot_req[slot].rid] \
                                .retry_ticks += 1
                    continue
                degraded = flags is not None and not _sync(
                    "flags", lambda: bool(flags["ok"]))
                nxt_np = _sync("tokens", lambda: np.asarray(nxt))
                for slot in range(self.B):
                    if not self.active[slot]:
                        continue
                    req = self.slot_req[slot]
                    out = self.outputs[req.rid]
                    out.tokens.append(int(nxt_np[slot]))
                    out.token_ticks.append(tick - 1)
                    out.uplink_bits += bits_per_tok
                    if degraded:
                        out.degraded_tokens += 1
                    self.budget[slot] -= 1
                    # the slot's device position, derived on the host: the
                    # prefill left it at prompt_len with one token out, and
                    # each committed tick adds one of each
                    done = (int(nxt_np[slot]) == self.eos
                            or self.budget[slot] <= 0
                            or out.prompt_len + len(out.tokens) - 1
                            >= self.max_seq - 1)
                    if done:
                        out.latency_ticks = tick - arrival_of[req.rid]
                        out.channel_slots = (
                            total_slots - slots_at_arrival[req.rid])
                        self._retire(slot)
        return self.outputs


def reference_tokens(model, values, requests: List[Request], max_seq: int,
                     eos_id: int = -1) -> Dict[int, List[int]]:
    """Channel-free greedy decode, one request at a time: a batch-1 jitted
    prefill, then batch-1 jitted decode steps under the engine's stopping
    rule (EOS, token budget, ``max_seq - 1``).  Channel-free serving must
    reproduce it; continuous batching and the fused tick must not perturb
    the decode.  (An eager ``decode_step`` would re-trace its inner scan
    every call, hence the jits.)"""
    prefill = jax.jit(
        lambda v, t: model.prefill(v, {"tokens": t}, max_seq=max_seq))
    decode = jax.jit(model.decode_step)
    out = {}
    for req in requests:
        tokens = jnp.asarray(req.prompt, jnp.int32)[None]
        logits, cache = prefill(values, tokens)
        tok = int(jnp.argmax(logits, -1)[0])
        toks = [tok]
        pos = len(req.prompt)
        budget = req.max_new_tokens - 1
        while tok != eos_id and budget > 0 and pos < max_seq - 1:
            logits, cache = decode(
                values, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray([pos], jnp.int32), cache)
            tok = int(jnp.argmax(logits, -1)[0])
            toks.append(tok)
            pos += 1
            budget -= 1
        out[req.rid] = toks
    return out


def _is_axes(x) -> bool:
    """A leaf of ``model.cache_axes()``: one tuple of axis names."""
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)

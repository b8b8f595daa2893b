"""Serving engine: slot lifecycle, budgets, decode consistency, and the
channel-in-the-loop path (Protocol aggregation inside the fused tick,
airtime accounting, Poisson load generation).

The redesign contracts pinned here:

  * channel-free serving is bit-for-bit the plain prefill+decode loop
    (the fused tick and continuous batching change nothing numerically),
  * refill/retire semantics: slots are reused after EOS, the length cap
    retires at ``max_seq``, a one-slot engine drains the queue FIFO,
  * ``Completion`` latency decomposition: ``latency_ticks`` spans arrival
    to retirement, ``channel_slots`` bills the measured shared-channel
    airtime, ``uplink_bits`` is the analytic per-request uplink — all
    three zero for channel-free serving,
  * sweeping channel quality rebinds only the protocol's traced ``p_miss``
    leaf: ONE compilation serves every point.
"""

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import faults, obs
from repro.configs import get_reduced
from repro.models import model as M
from repro.parallel.sharding import split_tree
from repro.protocol import Protocol
from repro.serve import engine as se
from repro.serve.engine import (ChannelClock, Completion, Request,
                                ServeConfig, ServeEngine)
from repro.serve.load import near_far_protocol, poisson_requests

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

N_WORKERS = 2
VOCAB = 64


@pytest.fixture(scope="module")
def model_and_values():
    cfg = get_reduced("qwen1.5-0.5b", n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=VOCAB,
                      n_workers=N_WORKERS)
    m = M.build(cfg)
    values, _ = split_tree(m.init(jax.random.PRNGKey(0)))
    return m, values


def _engine(m, values, **kw):
    return ServeEngine(m, values, ServeConfig(**kw))


def _ocs(p):
    return Protocol.ocs(bits=8,
                        p_miss=np.full((N_WORKERS,), p, np.float32))


def _manual_decode(m, values, prompt, max_new, max_seq, eos=-1):
    logits, cache = m.prefill(values, {"tokens": jnp.asarray(prompt)[None]},
                              max_seq=max_seq)
    tok = int(jnp.argmax(logits, -1)[0])
    toks = [tok]
    pos = len(prompt)
    budget = max_new - 1
    while tok != eos and budget > 0 and pos < max_seq - 1:
        logits, cache = m.decode_step(
            values, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([pos], jnp.int32), cache)
        tok = int(jnp.argmax(logits, -1)[0])
        toks.append(tok)
        pos += 1
        budget -= 1
    return toks


# -- refill / retire semantics ---------------------------------------------

def test_all_requests_complete(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=40, eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(3 + i, dtype=np.int32) % VOCAB,
                    max_new_tokens=6) for i in range(5)]
    outs = eng.run(reqs)
    assert set(outs) == set(range(5))
    for c in outs.values():
        assert len(c.tokens) == 6


def test_more_requests_than_slots_reuses_slots(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=1, max_seq=40, eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3) for i in range(3)]
    outs = eng.run(reqs)
    assert len(outs) == 3


def test_eos_retires_early_and_slot_is_reused(model_and_values):
    """Pick an actually-generated token as EOS: the request retires at its
    first occurrence and the freed slot still serves the queue behind it."""
    m, values = model_and_values
    prompt = np.arange(5, dtype=np.int32)
    ref = _manual_decode(m, values, prompt, 8, 40)
    eos = ref[2]                      # a token the decode provably emits
    # the first *decoded* occurrence retires the slot (the prefill token,
    # index 0, is produced by prefill and is not EOS-checked)
    stop_at = next(i for i in range(1, len(ref)) if ref[i] == eos) + 1
    assert stop_at < 8
    eng = _engine(m, values, batch_slots=1, max_seq=40, eos_id=eos)
    reqs = [Request(rid=i, prompt=prompt, max_new_tokens=8)
            for i in range(3)]
    outs = eng.run(reqs)
    assert set(outs) == {0, 1, 2}     # queue drained through the one slot
    for c in outs.values():
        assert c.tokens[-1] == eos
        assert len(c.tokens) == stop_at   # retired at EOS, not at budget


def test_length_cap_retires_at_max_seq(model_and_values):
    m, values = model_and_values
    prompt = np.arange(5, dtype=np.int32)
    eng = _engine(m, values, batch_slots=1, max_seq=8, eos_id=-1)
    out = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=100)])[0]
    # positions hits max_seq-1 after decoding max_seq - prompt_len tokens
    assert len(out.tokens) == 8 - len(prompt)


def test_one_slot_queue_drains_fifo(model_and_values):
    """With one slot, requests finish strictly in arrival order."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=1, max_seq=40, eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=4, arrival_tick=0) for i in range(4)]
    outs = eng.run(reqs)
    finish = [reqs[i].arrival_tick + outs[i].latency_ticks
              for i in range(4)]
    assert finish == sorted(finish)
    assert len(set(finish)) == 4      # strictly one-after-another


# -- channel-free parity ----------------------------------------------------

def test_greedy_serving_matches_manual_decode(model_and_values):
    """Engine output == direct prefill+argmax-decode, request by request,
    even when slots are shared (continuous batching is invisible)."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    prompts = [np.arange(5, dtype=np.int32),
               (np.arange(7, dtype=np.int32) * 3) % VOCAB,
               np.arange(4, dtype=np.int32) + 9]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=4)
            for i, p in enumerate(prompts)]
    outs = eng.run(reqs)
    for i, p in enumerate(prompts):
        assert outs[i].tokens == _manual_decode(m, values, p, 4, 32)


def test_channel_free_completion_has_zero_channel_fields(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    outs = eng.run([Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                            max_new_tokens=4)])
    c = outs[0]
    assert c.latency_ticks > 0
    assert c.channel_slots == 0 and c.uplink_bits == 0
    clock = ChannelClock(tick_us=50.0, slot_us=1.0)
    assert c.latency_us(clock) == c.latency_ticks * 50.0


# -- channel-in-the-loop ----------------------------------------------------

def test_channel_serving_bills_airtime_and_uplink(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1,
                  protocol=_ocs(0.05))
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    outs = eng.run(reqs)
    sites = m.channel_sites()
    per_tok = _ocs(0.05).comm_load(N_WORKERS, 32).uplink_bits * sites
    for c in outs.values():
        assert c.channel_slots > 0            # measured airtime
        # analytic uplink: only decode tokens cross the channel (the
        # prefill token comes from the channel-free prefill path)
        assert c.uplink_bits == (len(c.tokens) - 1) * per_tok


def test_error_free_channel_matches_ideal_max(model_and_values):
    """OCS at p_miss=0 serves the same tokens as Protocol.ideal_max."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(4 + i, dtype=np.int32),
                    max_new_tokens=4) for i in range(2)]
    under_ocs = eng.run(reqs, protocol=_ocs(0.0))
    ideal = eng.run(reqs, protocol=Protocol.ideal_max(8, tie_break="first"))
    for i in under_ocs:
        assert under_ocs[i].tokens == ideal[i].tokens


def test_p_miss_sweep_never_recompiles(model_and_values):
    """Rebinding the traced p_miss leaf reuses the compiled tick."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=4)]
    se.reset_trace_counts()
    eng.run(reqs, protocol=_ocs(0.0))
    eng.run(reqs, protocol=_ocs(0.3))
    eng.run(reqs, protocol=near_far_protocol(N_WORKERS, p_far=0.4))
    assert se.trace_counts()["tick"] == 1


def test_channel_serving_deterministic(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1,
                  protocol=_ocs(0.2))
    reqs = [Request(rid=i, prompt=np.arange(5, dtype=np.int32),
                    max_new_tokens=5) for i in range(2)]
    a = eng.run(reqs)
    b = eng.run(reqs)
    for i in a:
        assert a[i].tokens == b[i].tokens
        assert a[i].channel_slots == b[i].channel_slots


def test_one_dispatch_per_decode_tick(model_and_values):
    """Every decoded token row is covered by exactly the counted fused
    dispatches: dispatches in [ceil(tokens/B), tokens]."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=5) for i in range(3)]
    se.reset_dispatch_counts()
    outs = eng.run(reqs)
    ticks = se.dispatch_counts()["tick"]
    decode_tokens = sum(len(c.tokens) - 1 for c in outs.values())
    assert -(-decode_tokens // 2) <= ticks <= decode_tokens


# -- spans, per-token delivery ticks, stop hook -----------------------------

def _budget_requests():
    return [Request(rid=i, prompt=np.arange(4, dtype=np.int32) + i,
                    max_new_tokens=2 + i % 4) for i in range(7)]


@pytest.mark.parametrize("p_miss", [None, 0.1])
def test_host_syncs_per_tick_follow_the_loop(model_and_values, p_miss):
    """Every tick reads its tokens (and, with the channel, its airtime)
    once and nothing else: the stopping rule derives each slot's position
    on the host and never reads it back from the device."""
    m, values = model_and_values
    proto = None if p_miss is None else _ocs(p_miss)
    eng = _engine(m, values, batch_slots=3, max_seq=40, eos_id=-1,
                  protocol=proto)
    with obs.recording() as spans:
        outs = eng.run(_budget_requests())
    ticks = [(a["tick"], t0, t1) for n, t0, t1, a in spans
             if n == "serve.tick"]
    assert ticks
    for tick, t0, t1 in ticks:
        syncs = [a["what"] for n, s0, s1, a in spans
                 if n == "serve.sync" and t0 <= s0 and s1 <= t1]
        assert len(syncs) == 1 + (proto is not None)
        assert syncs.count("tokens") == 1
        assert syncs.count("airtime") == (proto is not None)
        assert "positions" not in syncs
    admits = [n for n, _, _, _ in spans if n == "serve.admit"]
    firsts = [a for n, _, _, a in spans
              if n == "serve.sync" and a["what"] == "first_token"]
    assert len(admits) == len(firsts) == len(outs)


def _retry_outage():
    # every worker drops and none recovers: the first ticks retry (holding
    # every position), then the exhausted budget commits degraded tokens
    return faults.FaultModel.iid(
        0.0, policy=faults.DegradePolicy.retry(2)).with_dropout(1.0, 0.0)


def _assert_positions_derived(eng):
    """A ``stop`` hook: before every tick, each active slot's device
    position is the one the host derives from its delivered tokens."""
    def stop():
        positions = np.asarray(eng.positions)
        for slot in np.flatnonzero(eng.active):
            out = eng.outputs[eng.slot_req[slot].rid]
            assert (out.prompt_len + len(out.tokens) - 1
                    == int(positions[slot]))
        return False
    return stop


@pytest.mark.parametrize("channel", ["off", "ocs", "retry_outage"])
def test_derived_position_matches_the_device_every_tick(model_and_values,
                                                         channel):
    m, values = model_and_values
    kw = {} if channel == "off" else {"protocol": _ocs(0.1)}
    if channel == "retry_outage":
        kw["fault"] = _retry_outage()
    eng = _engine(m, values, batch_slots=3, max_seq=40, eos_id=-1, **kw)
    outs = eng.run(_budget_requests(), stop=_assert_positions_derived(eng))
    for r in _budget_requests():
        assert len(outs[r.rid].tokens) == r.max_new_tokens
    if channel == "retry_outage":
        assert all(c.retry_ticks > 0 for c in outs.values()
                   if c.token_ticks[0] == 0)


def test_length_cap_retires_each_slot_at_max_seq(model_and_values):
    """Prompts of different lengths share the batch: each slot retires at
    ``max_seq - 1`` by its own derived position, with the reference's
    tokens."""
    m, values = model_and_values
    max_seq = 12
    reqs = [Request(rid=i, prompt=np.arange(3 + 2 * i, dtype=np.int32) + i,
                    max_new_tokens=100) for i in range(4)]
    eng = _engine(m, values, batch_slots=3, max_seq=max_seq, eos_id=-1)
    outs = eng.run(reqs, stop=_assert_positions_derived(eng))
    ref = se.reference_tokens(m, values, reqs, max_seq)
    for r in reqs:
        assert len(outs[r.rid].tokens) == max_seq - len(r.prompt)
        assert outs[r.rid].tokens == ref[r.rid]


def test_token_ticks_follow_the_harness_recorder(model_and_values):
    """One tick per token, never decreasing, and the decode ticks are
    those the benchmark's tick wrapper saw for the request."""
    from bench.drivers.serve import Recorder
    from bench.lib.harness import Spans

    m, values = model_and_values
    eng = _engine(m, values, batch_slots=3, max_seq=40, eos_id=-1,
                  protocol=_ocs(0.1))
    rec = Recorder(eng, Spans())
    try:
        outs = eng.run(_budget_requests())
    finally:
        rec.restore()
    ticks = rec.decode_ticks()
    for rid, c in outs.items():
        assert len(c.token_ticks) == len(c.tokens)
        assert c.token_ticks == sorted(c.token_ticks)
        assert c.token_ticks[1:] == ticks[rid][1]
        assert c.token_ticks[1] >= c.token_ticks[0]


def test_stop_ends_a_run_with_partial_completions(model_and_values):
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=40, eos_id=-1)
    full = {rid: list(c.tokens)
            for rid, c in eng.run(_budget_requests()).items()}
    asked = []

    def stop():
        asked.append(1)
        return len(asked) > 5

    se.reset_dispatch_counts()
    part = eng.run(_budget_requests(), stop=stop)
    assert len(asked) == 6
    # two asks per round (admission, tick): the third round's tick never ran
    assert se.dispatch_counts()["tick"] == 2
    assert 0 < len(part) < len(full)
    assert any(len(c.tokens) < len(full[rid]) for rid, c in part.items())
    for rid, c in part.items():
        assert c.tokens == full[rid][:len(c.tokens)]
        assert len(c.token_ticks) == len(c.tokens)


# -- load generation --------------------------------------------------------

def test_poisson_requests_shape_and_determinism():
    reqs = poisson_requests(16, 0.5, VOCAB, prompt_len=6,
                            max_new_tokens=4, seed=3)
    assert len(reqs) == 16
    arr = [r.arrival_tick for r in reqs]
    assert arr == sorted(arr) and arr[0] >= 0
    assert all(len(r.prompt) == 6 and r.prompt.dtype == np.int32
               and r.prompt.min() >= 0 and r.prompt.max() < VOCAB
               for r in reqs)
    again = poisson_requests(16, 0.5, VOCAB, prompt_len=6,
                             max_new_tokens=4, seed=3)
    assert [r.arrival_tick for r in again] == arr
    assert all(np.array_equal(a.prompt, b.prompt)
               for a, b in zip(reqs, again))


def test_poisson_requests_validation():
    with pytest.raises(ValueError):
        poisson_requests(0, 1.0, VOCAB)
    with pytest.raises(ValueError):
        poisson_requests(4, 0.0, VOCAB)


def test_late_arrivals_wait_for_their_tick(model_and_values):
    """A request arriving at tick T cannot retire before T."""
    m, values = model_and_values
    eng = _engine(m, values, batch_slots=2, max_seq=32, eos_id=-1)
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3, arrival_tick=0),
            Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3, arrival_tick=10)]
    outs = eng.run(reqs)
    # rid 1 decoded 2 tokens after arriving at tick 10
    assert outs[1].latency_ticks >= 2
    # and its tokens match the solo decode (queueing changes nothing)
    assert outs[1].tokens == _manual_decode(m, values, reqs[1].prompt, 3, 32)


def test_near_far_protocol_p_miss_profile():
    p = near_far_protocol(4, p_near=0.0, p_far=0.25)
    pm = np.asarray(p.p_miss)
    assert pm.shape == (4,) and pm.dtype == np.float32
    assert (pm[:2] == 0.0).all() and (pm[2:] == np.float32(0.25)).all()


# -- config surfaces --------------------------------------------------------

def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(batch_slots=0)
    with pytest.raises(ValueError):
        ServeConfig(max_seq=1)
    with pytest.raises(ValueError):
        ServeConfig(protocol=Protocol.concat())
    with pytest.raises(ValueError):
        ChannelClock(tick_us=0.0)
    with pytest.raises(ValueError):
        ChannelClock(slot_us=-1.0)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg = ServeConfig()
        cfg.batch_slots = 8


def test_completion_latency_decomposition():
    c = Completion(rid=0, tokens=[1, 2], prompt_len=3,
                   latency_ticks=7, channel_slots=120, uplink_bits=640)
    clock = ChannelClock(tick_us=10.0, slot_us=0.5)
    assert c.latency_us(clock) == 7 * 10.0 + 120 * 0.5

"""moonlight-16b-a3b (the DeepSeek-V3 block) at a small size in float32,
against the plain reference beside its benchmark configuration
(``bench/configs/moonlight-16b-a3b.ref.py``, which imports nothing of the
program): latent attention with its absorbed decode, the sigmoid router
with its selection-only bias, dropless routed experts, shared experts
through the channel, and the engine's cache scatter by declared axes.

Tolerances.  Program and reference both compute in float32 on the CPU and
differ only in the order of their sums: logits agree to about 1e-6 here.
``LOGIT_TOL`` = 1e-4 leaves two orders of room for that and is still tight
enough that the program in bfloat16 misses it by far
(``test_tolerance_is_tighter_than_bfloat16``).
"""

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config, get_reduced
from repro.models import mla, moe, transformer
from repro.models import model as M
from repro.parallel.sharding import split_tree
from repro.protocol import Protocol
from repro.serve.engine import Request, ServeConfig, ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.drivers import serve_moe  # noqa: E402
from bench.lib import harness as H  # noqa: E402

LOGIT_TOL = 1e-4
REF = H.load_file_module(ROOT / "bench" / "configs"
                         / "moonlight-16b-a3b.ref.py", "moonlight_ref")
SMALL = dict(n_layers=3, d_model=64, n_heads=4, d_ff=128, vocab_size=256,
             n_workers=2, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
             num_experts_per_tok=3, moe_intermediate_size=32,
             dtype="float32", initializer_range=0.1)


def small_config(**kw) -> dict:
    """The benchmark configuration's file at a small size: 1 dense + 2
    MoE layers, 8 experts top-3, 2 workers."""
    d = json.loads((ROOT / "bench" / "configs"
                    / "moonlight-16b-a3b.json").read_text())
    d.update(SMALL, **kw)
    return d


@pytest.fixture(scope="module")
def small():
    """``(config dict, model, values)``: the values made by the reference's
    ``init_params``, in the program's layout."""
    conf = small_config()
    m = M.build(serve_moe.model_config(conf))
    values = REF.init_params(conf, jax.random.PRNGKey(3))
    want, _ = split_tree(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    assert (jax.tree.structure(want)
            == jax.tree.structure(jax.eval_shape(lambda: values)))
    assert jax.tree.leaves(want) == jax.tree.leaves(
        jax.eval_shape(lambda: values))
    return conf, m, values


def teacher_forced(m, values, tokens, n_prompt):
    """Logits of positions n_prompt-1 .. T-1: a batch-1 prefill of the
    prompt, then one decode step per further token."""
    prefill = jax.jit(lambda v, t: m.prefill(v, {"tokens": t},
                                             max_seq=len(tokens)))
    decode = jax.jit(m.decode_step)
    logits, cache = prefill(values, jnp.asarray(tokens[:n_prompt])[None])
    out = [logits[0]]
    for t in range(n_prompt, len(tokens)):
        logits, cache = decode(values, jnp.asarray([[tokens[t]]]),
                               jnp.asarray([t], jnp.int32), cache)
        out.append(logits[0])
    return jnp.stack(out)


def test_prefill_then_decode_matches_the_reference(small):
    conf, m, values = small
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, conf["vocab_size"], 20).astype(np.int32)
    got = teacher_forced(m, values, tokens, 10)        # 10 decode steps
    want = jax.jit(lambda v, t: REF.forward(conf, v, t))(values, tokens)
    err = float(jnp.max(jnp.abs(got - want[9:])))
    assert err < LOGIT_TOL, err


def test_serve_engine_tokens_are_the_references_best(small):
    """Requests served by ``ServeEngine`` (batch-1 prefill, cache scatter,
    fused ticks over 3 slots, >= 8 decode steps each): every served
    token's reference logit lies within LOGIT_TOL of the reference's
    best at its position."""
    conf, m, values = small
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, conf["vocab_size"],
                                               6 + i).astype(np.int32),
                    max_new_tokens=10) for i in range(4)]
    eng = ServeEngine(m, values, ServeConfig(batch_slots=3, max_seq=24,
                                             eos_id=-1))
    outs = eng.run(reqs)
    fwd = jax.jit(lambda v, t: REF.forward(conf, v, t))
    for r in reqs:
        served = outs[r.rid].tokens
        assert len(served) == 10
        tokens = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        logits = fwd(values, tokens)[len(r.prompt) - 1:]
        gap = jnp.max(logits, -1) - jnp.take_along_axis(
            logits, jnp.asarray(served)[:, None], -1)[:, 0]
        assert float(jnp.max(gap)) < LOGIT_TOL, gap


def test_tolerance_is_tighter_than_bfloat16(small):
    """The same comparison with the program computing in bfloat16 misses
    LOGIT_TOL by orders of magnitude."""
    conf, _, values = small
    m16 = M.build(serve_moe.model_config(dict(conf, dtype="bfloat16")))
    layout, _ = split_tree(jax.eval_shape(m16.init, jax.random.PRNGKey(0)))
    v16 = jax.tree.map(lambda a, w: a.astype(w.dtype), values, layout)
    tokens = np.arange(12, dtype=np.int32) * 7 % conf["vocab_size"]
    got = teacher_forced(m16, v16, tokens, 6)
    want = REF.forward(conf, values, tokens)
    assert float(jnp.max(jnp.abs(got - want[5:]))) > 10 * LOGIT_TOL


def test_absorbed_decode_equals_expanded_on_the_same_cache():
    cfg = get_reduced("moonlight-16b-a3b")
    p, _ = split_tree(mla.mla_init(cfg, jax.random.PRNGKey(0)))
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    b, t = 3, 11
    q_nope = jax.random.normal(ks[0], (b, 1, cfg.n_heads,
                                       cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(ks[1], (b, 1, cfg.n_heads,
                                     cfg.qk_rope_head_dim))
    c_kv = jax.random.normal(ks[2], (b, t, cfg.kv_lora_rank))
    k_pe = jax.random.normal(ks[3], (b, t, cfg.qk_rope_head_dim))
    pos = jnp.asarray([3, 7, 10])
    mask = (jnp.arange(t)[None] <= pos[:, None])[:, None, :]
    absorbed = mla.attend_absorbed(cfg, p, q_nope, q_pe, c_kv, k_pe, mask)
    expanded = mla.attend_expanded(cfg, p, q_nope, q_pe, c_kv, k_pe, mask)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-5, atol=1e-5)


def test_skewed_router_prefill_drops_nothing(small):
    """A selection bias that sends every token to expert 0: a 24-token
    prompt's capacity-bounded dispatch (12 rows an expert) would drop half
    of them; the serving prefill matches the reference, and its trace took
    the dropless path."""
    conf, m, values = small
    ffn = values["blocks"]["pos0"]["ffn"]
    skewed = dict(values, blocks={"pos0": dict(
        values["blocks"]["pos0"],
        ffn=dict(ffn, select_bias=ffn["select_bias"].at[:, 0].set(10.0)))})
    tokens = np.random.default_rng(2).integers(
        0, conf["vocab_size"], 24).astype(np.int32)
    want = REF.forward(conf, skewed, tokens)[-1]
    obs.reset(moe.PATH_COUNTER)
    logits, _ = jax.jit(lambda v, t: m.prefill(v, {"tokens": t}))(
        skewed, jnp.asarray(tokens)[None])
    assert obs.counts(moe.PATH_COUNTER) == {moe.PATH_COUNTER + "dropless": 1}
    assert float(jnp.max(jnp.abs(logits[0] - want))) < LOGIT_TOL
    capped = m.logits(skewed, {"tokens": jnp.asarray(tokens)[None]})[0, -1]
    assert float(jnp.max(jnp.abs(capped - want))) > 100 * LOGIT_TOL


def test_selection_by_biased_scores_weighting_by_scores():
    cfg = get_config("moonlight-16b-a3b", n_experts=4, experts_per_token=2)
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    bias = jnp.asarray([0.0, 0.0, 1.0, 0.0])
    idx, w = moe.select(cfg, scores, bias)
    assert idx.tolist() == [[2, 0]]                 # by scores + bias
    np.testing.assert_allclose(w, [[0.1 / 1.0 * 2.446, 0.9 / 1.0 * 2.446]],
                               rtol=1e-6)           # by scores
    idx, w = moe.select(cfg.with_(moe_routed_scale=1.0), scores)
    assert idx.tolist() == [[0, 1]]
    np.testing.assert_allclose(w, [[0.9 / 1.7, 0.8 / 1.7]], rtol=1e-6)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _sliced_model(monkeypatch, cfg):
    """A fresh model whose stacks scan the routed experts as ``xs``, so
    that each MoE layer's matmuls take its (E, d, f) slices (the path
    before the stacks handed them the whole expert weights)."""
    monkeypatch.setattr(transformer, "stack_experts",
                        lambda cfg, values, plan: (values, {}))
    return M.build(cfg)


def test_whole_stack_experts_serve_the_sliced_paths_tokens(small,
                                                           monkeypatch):
    """The ragged matmuls over the whole stacked expert weights give the
    per-layer slices' results: teacher-forced prefill and decode logits,
    and every token the serve engine delivers, token for token."""
    conf, m, values = small
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, conf["vocab_size"], 18).astype(np.int32)
    reqs = [Request(rid=i, prompt=rng.integers(0, conf["vocab_size"],
                                               5 + 2 * i).astype(np.int32),
                    max_new_tokens=9) for i in range(4)]
    scfg = ServeConfig(batch_slots=3, max_seq=24, eos_id=-1)

    def serve(model):
        obs.reset(moe.WEIGHTS_COUNTER)
        logits = teacher_forced(model, values, tokens, 8)
        outs = ServeEngine(model, values, scfg).run(reqs)
        return (logits, {r: c.tokens for r, c in outs.items()},
                obs.counts(moe.WEIGHTS_COUNTER))

    got, got_tokens, got_counts = serve(m)
    want, want_tokens, want_counts = serve(_sliced_model(monkeypatch, m.cfg))
    assert set(got_counts) == {moe.WEIGHTS_COUNTER + "stacked"}
    assert set(want_counts) == {moe.WEIGHTS_COUNTER + "sliced"}
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    assert jnp.argmax(got, -1).tolist() == jnp.argmax(want, -1).tolist()
    assert got_tokens == want_tokens
    assert all(len(t) == 9 for t in got_tokens.values())


def test_rows_routed_to_the_last_layers_last_expert_all_arrive(small):
    """A selection bias that sends every token of the last MoE layer to
    its last expert, the last of the stack's L*E groups: prefill and
    decode still match the reference, so no row falls off the end of the
    group offsets."""
    conf, m, values = small
    ffn = values["blocks"]["pos0"]["ffn"]
    skewed = dict(values, blocks={"pos0": dict(
        values["blocks"]["pos0"],
        ffn=dict(ffn, select_bias=ffn["select_bias"].at[-1, -1].set(10.0)))})
    tokens = np.random.default_rng(5).integers(
        0, conf["vocab_size"], 24).astype(np.int32)
    layer = jax.tree.leaves(values["blocks"])[0].shape[0] - 1
    h = jnp.asarray(np.random.default_rng(6).standard_normal(
        (1, 24, conf["d_model"])), jnp.float32)
    p = jax.tree.map(lambda v: v[layer], skewed["blocks"]["pos0"]["ffn"])
    idx, _ = moe.select(m.cfg, moe.router_scores(m.cfg, p, h),
                        p["select_bias"])
    assert bool(jnp.all(jnp.any(idx == conf["n_routed_experts"] - 1, -1)))
    got = teacher_forced(m, skewed, tokens, 16)
    want = REF.forward(conf, skewed, tokens)[15:]
    assert float(jnp.max(jnp.abs(got - want))) < LOGIT_TOL


def test_tick_and_prefill_read_the_whole_expert_stacks(small):
    """No equation of the tick or the prefill holds one layer's (E, d, f)
    or (E, f, d) expert weights (no scan slice, no copy), and every ragged
    matmul's right-hand operand is the whole stack's (L*E, ...)."""
    conf, m, values = small
    e, d, f = (conf["n_routed_experts"], conf["d_model"],
               conf["moe_intermediate_size"])
    n = jax.tree.leaves(values["blocks"])[0].shape[0]
    b, s = 3, 10
    cache = m.cache_init(b, s)
    programs = {
        "tick": jax.make_jaxpr(m.decode_step)(
            values, jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
            cache),
        "prefill": jax.make_jaxpr(lambda v, t: m.prefill(
            v, {"tokens": t}, max_seq=s))(values, jnp.zeros((1, 8),
                                                            jnp.int32)),
    }
    one_layer = {(e, d, f), (e, f, d)}
    for name, jp in programs.items():
        eqns = list(_eqns(jp.jaxpr))
        rhs = [eqn.invars[1].aval.shape for eqn in eqns
               if eqn.primitive.name == "ragged_dot_general"]
        assert rhs == [(n * e, d, f), (n * e, d, f), (n * e, f, d)], name
        held = {v.aval.shape for eqn in eqns
                for v in list(eqn.invars) + list(eqn.outvars)
                if hasattr(v.aval, "shape")}
        held |= {v.aval.shape for eqn in eqns
                 for sub in jax.core.jaxprs_in_params(eqn.params)
                 for v in sub.invars}
        assert not held & one_layer, (name, held & one_layer)


def test_weights_counters_count_each_traced_moe_layer(small):
    """The tick and the prefill each trace the one scanned MoE position
    on the whole-stack path; no layer takes slices."""
    conf, m, values = small
    obs.reset(moe.WEIGHTS_COUNTER)
    eng = ServeEngine(m, values, ServeConfig(batch_slots=2, max_seq=12,
                                             eos_id=-1))
    eng.run([Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                     max_new_tokens=3)])
    assert obs.counts(moe.WEIGHTS_COUNTER) == {
        moe.WEIGHTS_COUNTER + "stacked": 2}


def test_dense_tick_counts_no_expert_weights():
    """qwen's stack has no MoE position: its tick and prefill count
    neither weights counter, and their scans keep every FFN leaf in xs."""
    m = M.build(get_reduced("qwen1.5-0.5b"))
    values, _ = split_tree(jax.eval_shape(m.init, jax.random.PRNGKey(0)))
    obs.reset(moe.WEIGHTS_COUNTER)
    b, s = 2, 8
    jax.make_jaxpr(m.decode_step)(values, jnp.zeros((b, 1), jnp.int32),
                                  jnp.zeros((b,), jnp.int32),
                                  m.cache_init(b, s))
    jax.make_jaxpr(lambda v, t: m.prefill(v, {"tokens": t}, max_seq=s))(
        values, jnp.zeros((1, 4), jnp.int32))
    assert obs.counts(moe.WEIGHTS_COUNTER) == {}
    rest, experts = transformer.stack_experts(
        m.cfg, values["blocks"], m.cfg.layer_plan())
    assert experts == {} and rest == values["blocks"]


@pytest.mark.parametrize("arch", ["qwen3-moe-30b-a3b",
                                  "jamba-1.5-large-398b"])
def test_scanned_and_unrolled_stacks_agree_on_the_stacked_path(arch):
    """The unrolled stack (``scan_layers=False``) hands the MoE layers the
    whole expert stacks as the scan does, and computes the same prefill
    and decode."""
    outs = {}
    for scan in (True, False):
        m = M.build(get_reduced(arch, scan_layers=scan))
        values, _ = split_tree(m.init(jax.random.PRNGKey(1)))
        tokens = jnp.asarray(np.random.default_rng(7).integers(
            0, m.cfg.vocab_size, (2, 9)), jnp.int32)
        obs.reset(moe.WEIGHTS_COUNTER)
        logits, cache = m.prefill(values, {"tokens": tokens[:, :8]},
                                  max_seq=9)
        step, _ = m.decode_step(values, tokens[:, 8:],
                                jnp.full((2,), 8, jnp.int32), cache)
        counted = obs.counts(moe.WEIGHTS_COUNTER)
        assert set(counted) == {moe.WEIGHTS_COUNTER + "stacked"}, counted
        outs[scan] = (logits, step)
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_expert_work_scales_with_routed_rows(small):
    """Prefill's routed experts are ragged matmuls over the T*k routed
    rows (padded up to the kernel's row tile), never a buffer of every
    expert over every token."""
    conf, m, values = small
    s, k = 40, conf["num_experts_per_tok"]
    jaxpr = jax.make_jaxpr(lambda v, t: m.prefill(v, {"tokens": t}))(
        values, jnp.zeros((1, s), jnp.int32))
    rows = [eqn.invars[0].aval.shape[0] for eqn in _eqns(jaxpr.jaxpr)
            if eqn.primitive.name == "ragged_dot_general"]
    tile = moe.ROW_TILE
    assert rows == [-(-s * k // tile) * tile] * 3
    assert rows[0] < s * conf["n_routed_experts"]


@pytest.mark.parametrize("m,k,n", [(37, 16, 8), (128, 32, 24),
                                   (5, 2048, 1100)],
                         ids=["padded", "whole_tile", "over_tile_bytes"])
def test_ragged_dot_equals_xlas_on_the_unpadded_rows(m, k, n):
    """``moe._ragged_dot`` (rows padded to the row tile, the kernel's
    tiling named where a group's weight fits) gives ``jax.lax.ragged_dot``
    on the rows as they are, up to the order of a float32 sum over k; the
    last group ends short of the rows."""
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    rows = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (3, k, n))
    sizes = jnp.asarray([m // 3, 0, m - m // 3 - 1], jnp.int32)
    assert (k * n * 4 > moe.WEIGHT_TILE_BYTES) == (k == 2048)
    np.testing.assert_allclose(moe._ragged_dot(rows, w, sizes),
                               jax.lax.ragged_dot(rows, w, sizes), rtol=0,
                               atol=2 * k * np.finfo(np.float32).eps)


def test_channel_sites_count_dense_and_shared_ffns():
    assert M.channel_sites(get_reduced("moonlight-16b-a3b")) == 3
    assert M.channel_sites(get_config("moonlight-16b-a3b", n_layers=6)) == 6
    assert M.channel_sites(get_config("qwen1.5-0.5b")) == 24
    assert M.channel_sites(get_config("qwen3-moe-30b-a3b")) == 0
    assert M.channel_sites(get_config("llama4-scout-17b-a16e")) == 48


def test_sum_protocol_decode_equals_decode_step(small):
    conf, m, values = small
    b, s = 2, 8
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, conf["vocab_size"], (b, s)), jnp.int32)
    _, cache = m.prefill(values, {"tokens": tokens}, max_seq=s + 1)
    tok, pos = tokens[:, -1:], jnp.full((b,), s, jnp.int32)
    want, want_cache = m.decode_step(values, tok, pos, cache)
    got, got_cache, chan = m.decode_step_channel(
        values, tok, pos, cache, Protocol.sum(), jax.random.PRNGKey(0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    jax.tree.map(lambda a, c: np.testing.assert_allclose(a, c, atol=1e-6),
                 got_cache, want_cache)
    assert int(chan["calls"]) == m.channel_sites() == 3


def test_ocs_pools_the_shared_experts_and_bills_every_site(small):
    """With OCS every worker-factored FFN (the dense layer and each MoE
    layer's shared expert) contends; served requests bill the analytic
    uplink of those sites for every decoded token."""
    conf, m, values = small
    n = conf["n_workers"]
    proto = Protocol.ocs(bits=8, p_miss=np.full((n,), 0.05, np.float32))
    tokens = jnp.asarray([[5, 9, 2, 7]], jnp.int32)
    _, cache = m.prefill(values, {"tokens": tokens}, max_seq=6)
    summed, _ = m.decode_step(values, tokens[:, -1:],
                              jnp.asarray([4]), cache)
    pooled, _, chan = m.decode_step_channel(
        values, tokens[:, -1:], jnp.asarray([4]), cache, proto,
        jax.random.PRNGKey(1))
    assert int(chan["calls"]) == 3 and int(chan["contention_slots"]) > 0
    assert float(jnp.max(jnp.abs(pooled - summed))) > 0
    eng = ServeEngine(m, values, ServeConfig(batch_slots=2, max_seq=16,
                                             eos_id=-1, protocol=proto))
    outs = eng.run([Request(rid=i, prompt=np.arange(4, dtype=np.int32) + i,
                            max_new_tokens=5) for i in range(3)])
    per_tok = proto.comm_load(n, conf["d_model"]).uplink_bits * 3
    for c in outs.values():
        assert c.uplink_bits == (len(c.tokens) - 1) * per_tok > 0


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "qwen1.5-0.5b"])
def test_insert_writes_the_slot_on_each_leafs_declared_batch_axis(arch):
    """The engine scatters an admitted request's cache on the batch axis
    ``model.cache_axes()`` declares: the latent cache (lead and main
    stacks) and qwen's K/V alike; every other slot is left as it was."""
    cfg = get_reduced(arch)
    m = M.build(cfg)
    values, _ = split_tree(m.init(jax.random.PRNGKey(0)))
    eng = ServeEngine(m, values, ServeConfig(batch_slots=3, max_seq=12,
                                             eos_id=-1))
    assert set(jax.tree.leaves(eng._batch_axes)) == {1}
    eng.cache = jax.tree.map(lambda a: jnp.full_like(a, 7), eng.cache)
    eng._insert(1, Request(rid=0, prompt=np.arange(5, dtype=np.int32)))
    for leaf in jax.tree.leaves(eng.cache):
        assert bool(jnp.all(leaf[:, 0] == 7)) and bool(
            jnp.all(leaf[:, 2] == 7))
        assert not bool(jnp.all(leaf[:, 1] == 7))


def test_serve_tick_and_prefill_trace_the_dropless_path(small):
    conf, m, values = small
    obs.reset(moe.PATH_COUNTER)
    eng = ServeEngine(m, values, ServeConfig(batch_slots=2, max_seq=12,
                                             eos_id=-1))
    eng.run([Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                     max_new_tokens=3)])
    counts = obs.counts(moe.PATH_COUNTER)
    # one MoE position in the scanned stack: one trace of the prefill,
    # one of the tick
    assert counts == {moe.PATH_COUNTER + "dropless": 2}

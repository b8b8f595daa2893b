"""The moonlight-16b-a3b serving cell's own pieces: the work counts of a
latent-attention MoE decoder by hand, the readers of its scope shares and
rooflines on a hand-built trace, the standing queue its window opens on,
and its check against the control and against planted faults at tiny
widths (the tiny run of the driver itself is a case of
``test_bench_drivers.py``)."""

import types

import bench_tiny
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve as S
from bench.drivers import serve_moe
from bench.lib import counts_moe as CM
from bench.lib import harness as H
from bench.lib import program_trace as P
from bench.lib import trace as T

CELL = "moonlight_serve_chat1k"
SMALL = CM.Shapes(n_lead=1, n_moe=2, d=4, heads=2, r=3, nope=2, rope=2, v=2,
                  d_ff=8, experts=4, k=2, f=3, f_shared=6, vocab=10,
                  itemsize=2)


def test_flops_of_a_token_and_a_prompt_by_hand():
    # q 2*(2+2), latent and k_pe 3+2, output 2*2, each 2*d FLOPs
    assert CM.projection_flops(SMALL) == 2 * 4 * 17
    assert CM.ffn_flops(SMALL, moe=False) == 6 * 4 * 8
    # router 2*4*4, two experts of 3 and the shared 6, SwiGLU 6*d*f
    assert CM.ffn_flops(SMALL, moe=True) == 32 + 6 * 4 * 12
    # lift 2*2*3*(2+2) = 48; absorbed scores and sums 2*2*ctx*(2*3+2),
    # expanded 2*2*ctx*(2+2+2)
    assert CM.attend_flops(SMALL, 5, absorbed=True) == 48 + 160
    assert CM.attend_flops(SMALL, 5, absorbed=False) == 48 + 120
    # 3 layers of (136 + 208), one dense FFN, two MoE FFNs, LM head 2*4*10
    assert CM.token_flops(SMALL, 5) == 3 * 344 + 192 + 640 + 80
    # positions 1 and 2 expanded, without heads, then one LM head
    assert CM.prefill_flops(SMALL, 2) == (3 * (136 + 72) + 832) + (
        3 * (136 + 96) + 832) + 80


def test_tick_work_of_experts_and_latent_attention_by_hand():
    # 5 slots x top-2 = 10 rows in 2 MoE layers; 4 experts x 3 matrices
    # of 4 x 3 in bf16, per MoE layer
    assert CM.experts_tick_work(SMALL, 5) == {"ops": 2 * 10 * 6 * 4 * 3,
                                              "bytes": 2 * 4 * 3 * 12 * 2}
    # contexts summing to 7 in 3 layers: 2 heads x 2*(2*3 + 2) FLOPs and
    # 3 + 2 cache values a position
    assert CM.attend_tick_work(SMALL, 7) == {"ops": 3 * 7 * 2 * 2 * 8,
                                             "bytes": 3 * 7 * 5 * 2}


def test_published_shapes_from_the_configuration():
    s = CM.shapes(H.resolve(CELL).config)
    assert (s.n_lead, s.n_moe, s.r, s.rope, s.experts, s.k) == (
        1, 5, 512, 64, 64, 6)
    # every routed expert of the 5 MoE layers in bf16: 5.54 GB a tick
    assert CM.experts_tick_work(s, 128)["bytes"] == 5_536_481_280
    # the latent cache of one position in all 6 layers: 6 x 1152 bytes
    assert CM.attend_tick_work(s, 1)["bytes"] == 6 * 1152


TICK_RAGGED = "%ragged-dot-none = bf16[768,1408] custom-call(%a, %w)"
PREFILL_RAGGED = "%ragged-dot-none = bf16[6144,1408] custom-call(%a, %w)"
# a layer's w_down sliced from the stack and copied, and the cache's copy
WEIGHT_COPY = ("%dynamic-slice_bitcast_fusion.8 = bf16[64,1408,2048]"
               "{2,1,0:T(8,128)(2,1)} fusion(%w, %i), kind=kLoop")
CACHE_COPY = "%copy.3 = bf16[128,2048,512]{2,1,0} copy(%c)"


def planes():
    """One chip, window 0..1000 ns: a tick program, a prefill, a tick
    (which copies a layer's expert weights), then a copy of the cache."""
    ev = types.SimpleNamespace
    ops = [(TICK_RAGGED, 0, 100), ("fusion.2", 100, 50),
           ("fusion.3", 150, 200), (PREFILL_RAGGED, 350, 50),
           ("fusion.5", 400, 200), (WEIGHT_COPY, 600, 100),
           (CACHE_COPY, 700, 100)]
    return [
        ev(name="/device:TPU:0", lines=[
            ev(name="XLA Ops", events=[ev(name=n, start_ns=s, duration_ns=d,
                                          stats=[]) for n, s, d in ops]),
            ev(name="XLA Modules", events=[
                ev(name="jit__tick(1)", start_ns=0, duration_ns=350,
                   stats=[]),
                ev(name="jit__lambda(2)", start_ns=350, duration_ns=50,
                   stats=[]),
                ev(name="jit__tick(1)", start_ns=400, duration_ns=300,
                   stats=[])])]),
        ev(name="/host:CPU", lines=[ev(name="python", events=[
            ev(name="bench.window", start_ns=0, duration_ns=1000,
               stats=[])])])]


# the ragged matmuls' metadata names only themselves, as XLA emits it
OP_STATS = {
    TICK_RAGGED: {"tf_op": "ragged-dot-none"},
    "fusion.2": {"tf_op": "jit(_tick)/while/body/moe.route/sort"},
    "fusion.3": {"tf_op": "jit(_tick)/while/body/mla.attend/dot_general"},
    PREFILL_RAGGED: {"tf_op": "ragged-dot-none"},
    "fusion.5": {"tf_op": "jit(_tick)/while/body/moe.experts/mul"},
    WEIGHT_COPY: {"tf_op": "jit(_tick)/while/body/squeeze"},
    CACHE_COPY: {"tf_op": ""},
}


@pytest.fixture
def traced(monkeypatch):
    raw = planes()
    pt = P.build(raw, OP_STATS)
    monkeypatch.setattr(P, "load", lambda run: pt)
    trace = T.from_planes(raw)
    return H.Run(e2e={}, attempted=0, failed=0, checks={}, device={},
                 summary=T.reduce_trace(trace),
                 layer={"device_kind": "TPU v5 lite", "tick_active": 100.0,
                        "tick_context_sum": 1000.0,
                        "tick_programs": CM.tick_programs(trace)})


def test_scope_shares_and_rooflines_on_a_hand_built_trace(traced):
    cell = H.resolve(CELL)
    read = {m: H.metric_reader(m)(traced, cell) for m in (
        "moe_experts_share.serve", "moe_route_share.serve",
        "mla_attend_share.serve", "moe_experts_roofline.serve",
        "mla_attend_roofline.serve")}
    # 800 ns of operations; the experts' are both ragged matmuls (the
    # prefill's too), the op under their scope and the weight copy, not
    # the cache's copy
    assert read["moe_experts_share.serve"] == pytest.approx(100 * 450 / 800)
    assert read["moe_route_share.serve"] == pytest.approx(100 * 50 / 800)
    assert read["mla_attend_share.serve"] == pytest.approx(100 * 200 / 800)
    # two ticks; the experts' 400 ns inside them, the attention's 200 ns
    s = CM.shapes(cell.config)
    peak = {"bf16": 197e12, "hbm": 819e9}
    ex = CM.experts_tick_work(s, 100.0)
    want = max(2 * ex["ops"] / peak["bf16"], 2 * ex["bytes"] / peak["hbm"])
    assert read["moe_experts_roofline.serve"] == pytest.approx(
        100 * want / 400e-9)
    at = CM.attend_tick_work(s, 1000.0)
    want = max(2 * at["ops"] / peak["bf16"], 2 * at["bytes"] / peak["hbm"])
    assert read["mla_attend_roofline.serve"] == pytest.approx(
        100 * want / 200e-9)


def test_readers_find_nothing_in_a_program_without_the_scopes(traced,
                                                              monkeypatch):
    """An older program (no MoE or MLA scopes, no ragged matmuls, no
    expert weights) reads nothing."""
    raw = planes()
    raw[0].lines[0].events = [e for e in raw[0].lines[0].events
                              if e.name not in (TICK_RAGGED, PREFILL_RAGGED,
                                                WEIGHT_COPY)]
    bare = P.build(raw, {})
    monkeypatch.setattr(P, "load", lambda run: bare)
    cell = H.resolve(CELL)
    for m in ("moe_experts_share.serve", "moe_route_share.serve",
              "mla_attend_share.serve", "moe_experts_roofline.serve",
              "mla_attend_roofline.serve"):
        assert H.metric_reader(m)(traced, cell) is None, m


def failed(run):
    """The check ran and found the outputs wrong."""
    return run.error is None and not run.correct


def chip_scale_cell():
    """The cell at tiny widths with ``initializer_range`` 0.2 and a 3 s
    window.  At d_model 64 the harness's tiny 0.08 leaves every logit gap
    near 0; 0.2 puts them on the scale the published widths read on a v5e
    chip (mean gap: program 0.01-0.03 here, 0.022-0.029 there; control
    0.41-0.62 here, 1.45-1.51 there), so the cell's own limits apply."""
    cell = bench_tiny.cell(CELL)
    cell.config["initializer_range"] = 0.2
    return cell


SEED = 2**33 + 11


def test_program_passes_and_the_control_fails_the_check():
    """The served tokens meet the cell's limits; the reference at float8
    operands in the program's place does not."""
    cell = chip_scale_cell()
    run = bench_tiny.run(cell, SEED, seconds=3.0)
    assert run.correct, {k: (c.value, c.limit) for k, c in run.checks.items()}
    served = serve_moe.build(cell, SEED)
    rec, _, _ = serve_moe.window(served, cell, SEED, 3.0, H.Spans())
    rids = serve_moe.sample(rec, SEED, cell.workload["check"]["tokens"])
    stats = S.gap_stats(serve_moe.logit_gaps(cell, served, rec, rids, "fp8"))
    limits = cell.workload["check"]["limits"]
    assert any(stats[k] > v for k, v in limits.items() if k in stats), stats


def planted(values, fault):
    """The served weights with a fault: the router's selection bias gone,
    or the shared experts' output projection zeroed."""
    ffn = values["blocks"]["pos0"]["ffn"]
    if fault == "no_bias":
        ffn = dict(ffn, select_bias=jnp.zeros_like(ffn["select_bias"]))
    else:
        shared = ffn["shared"]
        ffn = dict(ffn, shared=dict(shared, w_down=jnp.zeros_like(
            shared["w_down"])))
    return dict(values, blocks={"pos0": dict(values["blocks"]["pos0"],
                                             ffn=ffn)})


@pytest.mark.parametrize("fault", ["no_bias", "no_shared"])
def test_planted_faults_fail_the_check(monkeypatch, fault):
    """The program serves with the fault; the check's reference keeps the
    configuration's weights."""
    build = serve_moe.build

    def build_broken(cell, seed):
        served = build(cell, seed)
        served.engine.values = planted(served.values, fault)
        return served

    monkeypatch.setattr(serve_moe, "build", build_broken)
    assert failed(bench_tiny.run(chip_scale_cell(), SEED, seconds=3.0))


def test_driver_refuses_a_channel():
    cell = bench_tiny.cell(CELL, p_miss=0.05)
    with pytest.raises(ValueError, match="no channel"):
        serve_moe.build(cell, 1)


def test_driver_refuses_a_router_without_renormalised_weights():
    cell = bench_tiny.cell(CELL)
    cell.config["norm_topk_prob"] = False
    with pytest.raises(ValueError, match="renormalised"):
        serve_moe.model_config(cell.config)


def test_in_flight_requests_are_the_stationary_state_by_hand():
    """Two lengths, 1 and 3: of their 4 tokens one is the first's and
    three the second's; four stratified picks land one on each."""
    traffic = {"out_len": {"block": 2, "median": 1.732, "sigma": 2.0,
                           "min": 1, "max": 3}}
    age, rest = serve_moe.in_flight(traffic, 4)
    assert age.tolist() == [0, 0, 1, 2]
    assert rest.tolist() == [1, 3, 2, 1]


def test_published_queue_opens_in_steady_state():
    """At the cell's own sizes: the in-flight requests' ages weigh long
    outputs by their length, every output is served to its end within
    ``max_seq``, the prompt depths are the four the prefill compiles for,
    and the sizes are the same for every seed."""
    cell = H.resolve(CELL)
    b = cell.workload["engine"]["batch_slots"]
    age, rest = serve_moe.in_flight(cell.traffic, b)
    total = age + rest
    assert (rest >= 1).all() and total.max() <= cell.traffic["out_len"]["max"]
    # length-biased: the in-flight mean output is above the traffic's
    out = cell.traffic["out_len"]
    assert total.mean() > 1.3 * np.mean(S.T.lognormal_lengths(
        out["block"], out["median"], out["sigma"], out["min"], out["max"]))
    sizes = []
    for seed in (1, 2**33 + 3):
        cell.traffic["backlog"] = b + 5       # the sizes, not the queue
        reqs = serve_moe.queue(cell, seed)
        p_len = cell.traffic["prompt_len"]
        depth = [len(r.prompt) - p_len for r in reqs[:b]]
        assert set(depth) == {0, 256, 512, 768}
        assert all(len(r.prompt) == p_len for r in reqs[b:])
        assert all(len(r.prompt) + r.max_new_tokens
                   <= cell.workload["engine"]["max_seq"] for r in reqs)
        sizes.append(sorted(zip(depth, (r.max_new_tokens
                                        for r in reqs[:b]))))
    assert sizes[0] == sizes[1]


def test_window_opens_after_the_fill_and_the_first_tick():
    """Every slot's first admission and the first tick lie before the
    window; admissions inside it refill retired slots; the check draws
    from every depth the finished requests reached."""
    cell = bench_tiny.cell(CELL)
    served = serve_moe.build(cell, SEED)
    rec, t_start, t_end = serve_moe.window(served, cell, SEED, 2.0,
                                           H.Spans())
    b = cell.workload["engine"]["batch_slots"]
    fill = sorted(rec.inserts, key=lambda x: x[3])[:b]
    assert {slot for _, slot, _, _ in fill} == set(range(b))
    assert all(t1 < t_start for _, _, _, t1 in fill)
    assert rec.ticks[0][2] < t_start < rec.ticks[1][2]
    assert any(t_start < t1 <= t_end for _, _, _, t1 in rec.inserts)
    assert served.engine._tick.__name__ == "_tick"      # restored
    outs = served.engine.outputs
    rids = serve_moe.sample(rec, SEED, 10**6)          # every finished one
    depths = {outs[r].prompt_len for r in rids}
    assert len(rids) > b and len(depths) > 1

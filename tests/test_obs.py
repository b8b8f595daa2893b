"""repro.obs: one counter registry behind the engines' counter views, and
named host spans that count, record in memory and land in a profiler
trace."""

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_reduced
from repro.models import model as M
from repro.parallel.sharding import split_tree
from repro.serve import engine as se
from repro.serve.engine import Request, ServeConfig, ServeEngine
from repro.sim import sweep, train_curves

VIEWS = {
    "serve.trace.": (se.trace_counts, se.reset_trace_counts, ("tick",)),
    "serve.dispatch.": (se.dispatch_counts, se.reset_dispatch_counts,
                        ("tick",)),
    "sweep.trace.": (sweep.trace_counts, sweep.reset_trace_counts,
                     ("clean", "noisy")),
    "curves.trace.": (train_curves.trace_counts,
                      train_curves.reset_trace_counts,
                      ("fused", "sched", "fused_dp", "fused_faults")),
    "curves.dispatch.": (train_curves.dispatch_counts,
                         train_curves.reset_dispatch_counts,
                         ("fused", "sched", "fused_dp", "fused_faults")),
}


@pytest.mark.parametrize("prefix", sorted(VIEWS))
def test_counter_views_keep_their_keys_and_values(prefix):
    view, reset, keys = VIEWS[prefix]
    reset()
    assert view() == {k: 0 for k in keys}
    obs.count(prefix + keys[-1], 3)
    obs.count(prefix + keys[0])
    want = {k: 0 for k in keys}
    want[keys[-1]] += 3
    want[keys[0]] += 1
    assert view() == want
    others = {p: v[0]() for p, v in VIEWS.items() if p != prefix}
    reset()
    assert view() == {k: 0 for k in keys}
    assert {p: v[0]() for p, v in VIEWS.items() if p != prefix} == others


def test_a_span_counts_records_and_nests():
    obs.reset("t.")
    with obs.span("t.outer"):          # no recording: counted only
        pass
    with obs.recording() as rec:
        with obs.span("t.outer", k=1):
            with obs.span("t.inner", what="x"):
                pass
        with obs.recording() as deeper:
            with obs.span("t.inner"):
                pass
        with pytest.raises(ZeroDivisionError):
            with obs.span("t.failed"):
                1 / 0
    assert obs.counts("t.") == {"t.outer": 2, "t.inner": 2, "t.failed": 1}
    assert [(n, a) for n, _, _, a in rec] == [
        ("t.inner", {"what": "x"}), ("t.outer", {"k": 1}), ("t.failed", {})]
    (_, i0, i1, _), (_, o0, o1, _) = rec[:2]
    assert o0 <= i0 <= i1 <= o1
    assert [n for n, _, _, _ in deeper] == ["t.inner"]
    obs.reset("t.")
    assert obs.counts("t.") == {}


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = get_reduced("qwen1.5-0.5b", n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab_size=64, n_workers=2)
    m = M.build(cfg)
    values, _ = split_tree(m.init(jax.random.PRNGKey(0)))
    return ServeEngine(m, values, ServeConfig(batch_slots=2, max_seq=32,
                                              eos_id=-1))


def _requests():
    return [Request(rid=i, prompt=np.arange(4, dtype=np.int32) + i,
                    max_new_tokens=3 + i) for i in range(3)]


def test_serve_spans_match_the_dispatch_count(tiny_engine):
    tiny_engine.run(_requests())             # compile outside the count
    se.reset_dispatch_counts()
    obs.reset("serve.")
    tiny_engine.run(_requests())
    ticks = se.dispatch_counts()["tick"]
    assert ticks > 0
    assert obs.counts("serve.tick") == {"serve.tick": ticks}
    assert obs.counts("serve.admit") == {"serve.admit": 3}


def test_profiler_trace_holds_the_serve_spans_on_a_host_plane(
        tiny_engine, tmp_path):
    tiny_engine.run(_requests())
    with jax.profiler.trace(str(tmp_path)):
        tiny_engine.run(_requests())
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro.serve."):
                    assert plane.name.startswith("/host:")
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(found) == {"repro.serve.admit", "repro.serve.tick",
                          "repro.serve.sync"}
    assert sorted(s["rid"] for s in found["repro.serve.admit"]) == [0, 1, 2]
    assert [s["tick"] for s in found["repro.serve.tick"]] == list(
        range(len(found["repro.serve.tick"])))
    assert {s["what"] for s in found["repro.serve.sync"]} == {
        "tokens", "first_token"}

"""The program's spans and name scopes in a trace, and the readers of the
metrics built on them, on a hand-built two-chip trace."""

import json
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import harness as H  # noqa: E402
from bench.lib import program_trace as P  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
RAW = json.loads((HERE / "trace_program_small.json").read_text())


def planes():
    """The committed trace as objects shaped like ProfileData planes."""
    ev = types.SimpleNamespace
    return [ev(name=p["name"], lines=[
        ev(name=ln["name"], events=[
            ev(name=e[0], start_ns=e[1], duration_ns=e[2],
               stats=list(e[3].items()) if len(e) > 3 else [])
            for e in ln["events"]])
        for ln in p["lines"]]) for p in RAW["planes"]]


@pytest.fixture
def pt():
    return P.build(planes(), RAW["op_stats"])


def test_program_spans_overlapping_the_window_with_their_attributes(pt):
    assert pt.window == (100, 1100)
    assert [(n, s, e) for n, s, e, _ in pt.spans] == [
        ("serve.tick", 50, 600), ("serve.sync", 550, 650),
        ("serve.tick", 620, 1000), ("serve.sync", 640, 660),
        ("serve.sync", 900, 1000), ("serve.admit", 1000, 1080),
        ("serve.sync", 1060, 1080), ("serve.tick", 1090, 1150)]
    assert pt.spans[1][3] == {"what": "tokens"}
    assert pt.spans[5][3] == {"rid": 3, "slot": 1}
    assert [s for _, s, _, _ in pt.starting("serve.tick")] == [620, 1090]
    assert [e for _, _, e, _ in pt.ending("serve.admit")] == [1080]
    assert pt.covered("serve.sync") == [(550, 660), (900, 1000),
                                        (1060, 1080)]


def test_ops_carry_their_scope_path_and_leave_containers_out(pt):
    ops = pt.ops["/device:TPU:0"]
    assert [(t[:8], s, e) for t, s, e, _ in ops] == [
        ("fusion.1", 100, 300), ("fusion.2", 300, 400),
        ("%custom-", 400, 450), ("fusion.4", 450, 600),
        ("fusion.6", 700, 800), ("copy.7", 1050, 1100)]
    paths = [p for _, _, _, p in ops]
    assert paths[0].startswith("jit(_tick)/while/body/protocol.aggregate/")
    assert paths[3] == "jit(_tick)/mlp/dot_general:"
    assert paths[5] == ""                                 # no stats
    assert pt.ops["/device:TPU:1"] == [("fusion.9", 100, 1100, "")]


@pytest.mark.parametrize("path, scope, inside", [
    ("jit(_tick)/protocol.aggregate/ocs.sense/xor:", "protocol.aggregate",
     True),
    ("jit(_tick)/protocol.aggregate/ocs.sense/xor:", "ocs.sense", True),
    ("jit(step)/jvp(protocol.aggregate)/pallas_call:", "protocol.aggregate",
     True),
    ("jit(step)/transpose(jvp(protocol.aggregate))/mul:",
     "protocol.aggregate", True),
    ("jit(f)/protocol.aggregate", "protocol.aggregate", True),
    ("jit(_tick)/protocol.aggregate_extra/add:", "protocol.aggregate",
     False),
    ("jit(_tick)/xprotocol.aggregate/add:", "protocol.aggregate", False),
    ("jit(_tick)/mlp/dot_general", "ocs.sense", False),
    ("", "ocs.sense", False),
])
def test_scope_matching_on_op_stats(path, scope, inside):
    assert P.in_scope(path, scope) is inside


def test_scope_shares_of_device_time(pt):
    # device time 650 (chip 0) + 1000 (chip 1); under protocol.aggregate
    # fusion.1, fusion.2 and the kernel (350), under ocs.sense fusion.1
    assert P.scope_share(pt, "protocol.aggregate") == pytest.approx(
        100 * 350 / 1650)
    assert P.scope_share(pt, "ocs.sense") == pytest.approx(100 * 200 / 1650)
    assert P.scope_share(pt, "nowhere") is None
    assert P.scope_share(None, "ocs.sense") is None


def test_idle_inside_spans_on_a_known_gap_layout(pt):
    # chip 0 idles 600..700 and 800..1050; the syncs cover 550..660,
    # 900..1000 and 1060..1080: idle inside 600..660 and 900..1000;
    # the admission 1000..1080 idles 1000..1050; chip 1 never idles
    assert P.idle_inside_ns(pt, "serve.sync") == (160 + 0) / 2
    assert P.idle_inside_ns(pt, "serve.admit") == (50 + 0) / 2
    # two ticks start in the window
    assert P.idle_inside_ms_per_tick(pt, "serve.sync") == pytest.approx(
        80 / 2 / 1e6)


def test_per_tick_counts_and_mean_durations(pt):
    assert P.per_tick(pt, "serve.sync") == 4 / 2
    assert P.per_tick(pt, "serve.admit") == 1 / 2
    assert P.mean_ms(pt, "serve.admit") == pytest.approx(80 / 1e6)
    assert P.mean_ms(pt, "serve.nothing") is None
    assert P.per_tick(None, "serve.sync") is None
    assert P.mean_ms(None, "serve.admit") is None


READINGS = {
    "serve.host_syncs_per_tick": 2.0,
    "serve.sync_idle_ms": 80 / 2 / 1e6,
    "serve.admit_ms": 80 / 1e6,
    "channel_share.train": 100 * 350 / 1650,
    "channel_share.serve": 100 * 350 / 1650,
    "ocs_sense_share.train": 100 * 200 / 1650,
    "ocs_sense_share.serve": 100 * 200 / 1650,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_each_reader_reads_the_program_trace(name, pt, monkeypatch):
    monkeypatch.setattr(P, "load", lambda run: pt)
    run = H.Run(e2e={}, attempted=0, failed=0, checks={}, device={})
    assert H.metric_reader(name)(run, None) == pytest.approx(READINGS[name])


def test_readers_find_nothing_without_program_spans_or_scopes(monkeypatch):
    """A program without spans or scopes (an older commit) reads nothing."""
    raw = planes()
    for plane in raw:
        for line in plane.lines:
            line.events = [e for e in line.events
                           if not e.name.startswith("repro.")]
    bare = P.build(raw, {})
    monkeypatch.setattr(P, "load", lambda run: bare)
    run = H.Run(e2e={}, attempted=0, failed=0, checks={}, device={})
    for name in READINGS:
        assert H.metric_reader(name)(run, None) is None, name


def test_op_stats_decoded_from_a_serialized_xspace(tmp_path):
    space = P._xspace_class()()
    dev = space.planes.add(name=b"/device:TPU:0")
    for key, name in ((1, b"tf_op"), (2, b"hlo_category"),
                      (3, b"jit(_tick)/protocol.aggregate/or:")):
        e = dev.stat_metadata.add(key=key)
        e.value.name = name
    md = dev.event_metadata.add(key=7).value
    md.name = b"%fusion.1 = u32[4] fusion()"
    md.stats.add(metadata_id=1, ref_value=3)
    md.stats.add(metadata_id=2, str_value=b"loop fusion")
    host = space.planes.add(name=b"/host:CPU")
    host.event_metadata.add(key=1).value.name = b"repro.serve.tick"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert P.read_op_stats(path) == {"%fusion.1 = u32[4] fusion()": {
        "tf_op": "jit(_tick)/protocol.aggregate/or:",
        "hlo_category": "loop fusion"}}

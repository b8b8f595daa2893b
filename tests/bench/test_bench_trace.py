"""The benchmark's trace reduction on a hand-built two-chip trace."""

import json
import pathlib
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import trace as T  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent


def planes():
    """The committed trace as objects shaped like ProfileData planes."""
    raw = json.loads((HERE / "trace_small.json").read_text())
    ev = types.SimpleNamespace
    return [ev(name=p["name"], lines=[
        ev(name=ln["name"], events=[ev(name=n, start_ns=s, duration_ns=d)
                                    for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]]


def summary():
    return T.reduce_trace(T.from_planes(planes()))


def test_planes_split_into_devices_programs_and_harness_spans():
    tr = T.from_planes(planes())
    assert sorted(tr.ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(tr.ops["/device:TPU:0"]) == 6
    assert [n for n, _, _ in tr.spans] == ["bench.window", "bench.tick",
                                           "bench.insert", "bench.fetch"]
    assert T.window_of(tr) == (100, 1100)


def test_interval_union_and_subtraction():
    assert T.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert T.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert T.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [
        (0, 2), (4, 8), (22, 30)]
    assert T.clip([("a", 0, 50), ("b", 60, 70)], (40, 65)) == [
        ("a", 40, 50), ("b", 60, 65)]


def test_busy_union_and_idle_share_averaged_over_chips():
    s = summary()
    assert s.window_ns == 1000
    assert s.n_devices == 2
    # chip 0 busy 100..600, 700..800, 1050..1100; chip 1 the whole window
    assert s.busy_ns == (650 + 1000) / 2
    assert s.busy_s == 825e-9


def test_device_time_by_operation_and_program():
    s = summary()
    assert s.op_ns == {"fusion.1": 400, "fusion.2": 200, "all-gather.3": 250,
                       "custom-call.7": 100, "all-reduce.1": 200,
                       "fusion.9": 650}
    assert s.ops_matching("tpu_custom_call") == 100
    assert s.ops_matching("while") == 0
    assert s.module_ns == {"jit__tick(1)": 500 + 450 + 1000}
    assert s.module_count == {"jit__tick(1)": 3}


def test_collective_time_with_no_compute_beside_it():
    # chip 0: all-gather 350..600, compute until 400 -> 200 exposed;
    # chip 1: all-reduce 300..500, compute again from 450 -> 150 exposed
    assert summary().collective_exposed_ns == (200 + 150) / 2


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = summary()
    # gap 600..700: tick covers 20 ns, insert 80 -> insert;
    # gap 800..1050: insert covers 200, fetch 50 -> insert
    assert s.gaps == [("insert", 250), ("insert", 100)]
    assert T.attribute((5, 6), [("bench.window", 0, 10)]) == "none"
    b = s.breakdown(top=2)
    assert b["device_ops"] == [["fusion.9", 650e-9], ["fusion.1", 400e-9]]
    assert b["idle_gaps"] == [["insert", 250e-9], ["insert", 100e-9]]

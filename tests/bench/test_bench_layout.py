"""BENCHMARK.json and the files it names: every cell finds its config,
reference, traffic, workload, driver and metric readers by name."""

import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import harness as H  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert H.applies(next(e for e in BENCH["end_to_end"]
                                  if e["name"] == m["moves"]), cell), (
                f"{m['name']} lists {cell}, which does not report "
                f"{m['moves']}")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    c = H.resolve(cell, BENCH)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    assert (ROOT / "bench" / "configs" / f"{c.config_name}.ref.py").is_file()
    assert (ROOT / "bench" / "drivers"
            / f"{c.workload['driver']}.py").is_file()
    assert c.traffic["kind"] in ("curve_sweep", "serve_backlog")
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer
    for name in c.per_layer:
        assert callable(H.metric_reader(name))
    assert set(c.workload["check"]["limits"])


def test_every_config_is_used_and_names_its_file():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        files.add(c["file"])
    assert len(files) == len(BENCH["configs"])


def test_readers_find_nothing_in_an_untraced_run():
    run = H.Run(e2e={}, attempted=0, failed=0, checks={}, device={})
    for m in BENCH["per_layer"]:
        cell = H.resolve(m.get("workloads", CELLS)[0], BENCH)
        assert H.metric_reader(m["name"])(run, cell) is None, m["name"]


def test_check_needs_every_number_within_its_limit():
    ok = H.Run(e2e={}, attempted=1, failed=0, device={},
               checks={"a": H.Check(0.5, 1.0), "b": H.Check(0.0, 0.0)})
    assert ok.correct
    assert not H.Run(e2e={}, attempted=1, failed=0, device={},
                     checks={"a": H.Check(1.5, 1.0)}).correct
    assert not H.Run(e2e={}, attempted=1, failed=0, device={},
                     checks={"a": H.Check(float("nan"), 1.0)}).correct
    assert not H.Run(e2e={}, attempted=1, failed=0, device={},
                     checks={}).correct

"""Each driver's set-up, window and check at tiny widths on the CPU, the
result line, and the refusals of ``bench/run.py``."""

import json
import os
import shutil
import subprocess
import sys

import bench_tiny
import pytest

from bench import run as run_py
from bench.drivers import curves, serve
from bench.lib import harness as H

ROOT = bench_tiny.ROOT
BENCH = H.benchmark()
UNITS = {m["name"]: m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}


CELLS = [(w["name"], None) for w in BENCH["workloads"]] + [
    ("qwen05_serve_nochannel", 0.05)]    # the serve path through the channel


@pytest.mark.parametrize("name,p_miss", CELLS)
def test_window_and_check_at_tiny_widths(name, p_miss):
    cell = bench_tiny.cell(name, p_miss)
    run = bench_tiny.run(cell)
    assert run.error is None, run.error
    assert run.correct, {k: (c.value, c.limit) for k, c in run.checks.items()}
    assert run.attempted > 0 and run.failed == 0
    assert run.layer["compiles_in_window"] == 0
    for metric in cell.end_to_end:
        assert run.e2e[metric] > 0, metric
    line = run_py.result_line(cell, run, UNITS, tracing=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == set(cell.end_to_end)
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())
    json.dumps(line)


def run_py_in(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cifar10_ocs_sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = run_py_in(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_without_the_system_under_test(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py_in(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_serve_window_stamps_tokens_at_the_loops_own_sync():
    """Every tick's tokens get the time the engine's loop read them to the
    host, in order, and the wrapper hands the loop the tick's own array."""
    cell = bench_tiny.cell("qwen05_serve_nochannel")
    served = serve.build(cell, 2**33 + 5)
    serve.warm_up(served, cell, 2**33 + 5)
    rec, t_start, t_end = serve.window(served, cell, 2**33 + 5, 0.5,
                                       H.Spans())
    stamps = [t for _, _, t in rec.ticks]
    assert stamps and all(t is not None for t in stamps)
    assert stamps == sorted(stamps)
    assert t_start <= stamps[0] and stamps[-1] <= t_end
    assert served.engine._tick.__name__ == "_tick"      # restored


def test_train_check_reads_the_planted_faults():
    """The whole-run numbers: the program meets its reference, a state
    left unchanged reads a parameter change gap of 1."""
    cell = bench_tiny.cell("cifar10_ocs_sweep")
    eng = curves.build(cell, 2**33 + 9)
    want = curves.reference_out(cell, eng, 0)
    n = cell.workload["check"]["steps"]
    prog = curves.gaps(want, curves.dispatch(eng, 0), eng.params0, n)
    assert prog["param_change_gap"] < 1e-5 and prog["nll_gap"] < 1e-5
    unchanged = curves.gaps(want, curves.reference_out(
        cell, eng, 0, "highest", "unchanged"), eng.params0, n)
    assert unchanged["param_change_gap"] == 1.0
    assert unchanged["param_leaves_left_out"] == 0

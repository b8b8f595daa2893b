"""The check's control and its faults: each must make ``correct`` false.

The control is the cell's plain reference computed one step of precision
below the configuration's (float8 operands) and put in the program's
place.  The faults break the timed path underneath a run that skips only
the harness's look for a chip: a training step that returns its state
unchanged, a step that takes the mean over half of its batch, a served
token altered where the tick produces it, and a tick that returns its KV
cache unchanged.  On the chip, ``bench/calibrate.py`` reads the control
at each cell's own size."""

import bench_tiny
import pytest

from bench.drivers import curves, serve
from repro.sim import train_curves as tc


def failed(run):
    """The check ran and found the outputs wrong."""
    return run.error is None and not run.correct


def test_train_control_fails_its_check():
    cell = bench_tiny.cell("cifar10_ocs_sweep")
    eng = curves.build(cell, 2**33 + 1)
    ctrl = curves.check(cell, eng, {0: curves.control_out(cell, eng, 0)})
    prog = curves.check(cell, eng, {0: curves.dispatch(eng, 0)})
    assert all(c.ok for c in prog.values())
    assert not all(c.ok for c in ctrl.values()), {
        k: (c.value, c.limit) for k, c in ctrl.items()}


def broken_step(step, fault):
    def unchanged(values, opt_state, batch, *rest):
        return (values, opt_state) + tuple(step(values, opt_state, batch,
                                                *rest)[2:])

    def half_batch(values, opt_state, batch, *rest):
        views, labels = batch
        h = labels.shape[0] // 2
        return step(values, opt_state, (views[:, :h], labels[:h]), *rest)

    return {"unchanged": unchanged, "half_batch": half_batch}[fault]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults_fail_the_check(monkeypatch, fault):
    make = tc._make_steps

    def make_broken(ccfg, bits):
        vn, vi, opt, step_n, step_i = make(ccfg, bits)
        return (vn, vi, opt, broken_step(step_n, fault),
                broken_step(step_i, fault))

    monkeypatch.setattr(tc, "_make_steps", make_broken)
    assert failed(bench_tiny.run(bench_tiny.cell("cifar10_ocs_sweep")))


def broken_tick(tick, fault, vocab):
    def altered_token(*args):
        nxt, *rest = tick(*args)
        return ((nxt + 1) % vocab, *rest)

    def unchanged_cache(*args):
        nxt, pos, _cache, *rest = tick(*args)
        return (nxt, pos, args[6], *rest)

    return {"token": altered_token, "cache": unchanged_cache}[fault]


@pytest.mark.parametrize("p_miss", [None, 0.05])
@pytest.mark.parametrize("fault", ["token", "cache"])
def test_serve_faults_fail_the_check(monkeypatch, p_miss, fault):
    build = serve.build

    def build_broken(cell, seed):
        served = build(cell, seed)
        served.engine._tick = broken_tick(served.engine._tick, fault,
                                          cell.config["vocab_size"])
        return served

    monkeypatch.setattr(serve, "build", build_broken)
    assert failed(bench_tiny.run(bench_tiny.cell("qwen05_serve_nochannel",
                                                 p_miss)))


@pytest.mark.parametrize("fault", ["token", "cache"])
def test_serve_ocs_cell_faults_fail_its_check(monkeypatch, fault):
    """The channel serving cell, with its own reference precision and
    limits.  Its prompts are cut to two tokens: at these widths a lost
    cache write shows only where nearly every position is decoded."""
    build = serve.build

    def build_broken(cell, seed):
        served = build(cell, seed)
        served.engine._tick = broken_tick(served.engine._tick, fault,
                                          cell.config["vocab_size"])
        return served

    monkeypatch.setattr(serve, "build", build_broken)
    cell = bench_tiny.cell("qwen05_serve_ocs")
    cell.traffic["prompt_len"] = 2
    assert failed(bench_tiny.run(cell))

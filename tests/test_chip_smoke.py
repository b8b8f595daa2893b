"""``chip_smoke.py``: its refusals, and its phases' checks at tiny widths.

On the CPU the script must refuse (non-zero exit, no JSON result line), and
so must a copy of it standing alone without the repo.  The phases themselves
run here at tiny widths with the kernels interpreted, so a change to the
train or serve API that breaks a check shows here before it costs chip time.
"""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"
TINY = dict(grid=2, hw=8, n_classes=4, encoder_dims=(16,), embed_dim=8,
            head_dims=(16,))


def _run(args, cwd, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_a_tpu_or_the_repo(tmp_path, where):
    if where == "alone":
        shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
        script = tmp_path / SCRIPT.name
    else:
        script = SCRIPT
    proc = _run([str(script)], cwd=tmp_path)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
    assert "chip_smoke:" in proc.stderr


def test_train_and_serve_phases_at_tiny_widths(cs, tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(cs, "IR_DIR", tmp_path)
    clock = cs.CompileClock(jax)
    cs.phase_train(0, clock, steps=10, batch=32, widths=TINY,
                   expect_kernel=False)
    cs.phase_serve(0, clock, argv=[
        "--arch", "qwen1.5-0.5b", "--smoke", "--batch-slots", "2",
        "--max-seq", "32", "--eos-id", "-1", "--p-miss", "0.05",
        "--bits", "8", "--max-rounds", "3", "--backend", "pallas",
        "--requests", "4", "--rate", "1.0", "--prompt-len", "4",
        "--max-new", "6", "--seed", "0"])


def test_four_chips_phase_at_tiny_widths(tmp_path):
    code = textwrap.dedent(f"""
        import importlib.util, pathlib, sys
        sys.path.insert(0, {str(ROOT / "src")!r})
        spec = importlib.util.spec_from_file_location("cs", {str(SCRIPT)!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        import jax
        cs.IR_DIR = pathlib.Path({str(tmp_path)!r})
        jax.config.update("jax_dump_ir_to", str(cs.IR_DIR))
        cs.phase_four_chips(0, cs.CompileClock(jax), steps=6, batch=32,
                            widths={TINY!r})
    """)
    proc = _run(["-c", code], cwd=tmp_path, devices=4)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    assert "check FAIL" not in proc.stdout
    assert "holds the all-gather" in proc.stdout

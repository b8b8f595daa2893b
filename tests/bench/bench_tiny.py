"""The benchmark's cells at tiny widths, for tests on the CPU.

Each cell keeps its files' structure with every size cut, the contention on
the ``scan`` backend (bit for bit the Pallas kernel, without the
interpreter's cost), and the window's loop and check as a run drives them.
"""

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import harness as H  # noqa: E402


def cell(name: str, p_miss=None) -> H.Cell:
    """The cell at tiny widths; a serve cell with ``p_miss`` set serves
    through the OCS channel (the configuration's channel structure)."""
    c = H.resolve(name)
    if c.workload["driver"] == "curves":
        c.config.update(encoder_dims=[32], embed_dim=8, head_dims=[32],
                        hw=16)
        c.config["aggregation"]["backend"] = "scan"
        c.traffic.update(batch=16, steps_per_dispatch=6, n_train=64,
                         n_val=32)
        c.traffic["p_miss"]["lanes"] = 4
    else:
        c.config.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        head_dim=16, d_ff=128, vocab_size=512, n_workers=4,
                        initializer_range=0.08)
        c.config["channel"]["backend"] = "scan"
        c.traffic.update(prompt_len=8, backlog=400)
        c.traffic["out_len"].update(median=6, min=2, max=12)
        c.workload["engine"].update(batch_slots=4, max_seq=32)
        c.workload["check"]["tokens"] = 40
        if p_miss is not None:
            c.traffic["p_miss"] = p_miss
    return c


def run(c: H.Cell, seed: int = 2**33 + 7, seconds: float = 0.5,
        tracing: bool = False) -> H.Run:
    """One run of the cell's driver past the harness's look for a chip."""
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return c.driver().run(c, seed=seed, seconds=seconds, tracing=tracing,
                          t0=time.perf_counter(), clock=H.CompileClock(),
                          device=device)

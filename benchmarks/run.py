"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_table1    paper Table I  (5 aggregation methods, patch classification)
  bench_fig2      paper Fig. 2   (multi-sensor denoising, 1 vs 4 workers)
  bench_comm      paper §I claim (O(K) vs O(N*K) comm; ICI fusion bytes)
  bench_sweep     batched scenario sweep (repro.sim) over N x bits x p_miss
  bench_curves    channel-in-the-loop training: accuracy vs p_miss x bits
  bench_serve     channel-in-the-loop serving: tokens/sec + latency vs p_miss
  bench_contention  noisy-contention backends: lax.scan vs fused Pallas
  bench_kernels   Pallas kernel micro-timings (interpret mode)
  bench_roofline  roofline terms per (arch x shape) from dry-run artifacts

Full (non ``--fast``) runs additionally persist their numbers as canonical
``BENCH_*.json`` files at the repo root (``BENCH_serve.json``,
``BENCH_contention.json``); ``--fast`` leaves the committed full-scale
numbers untouched.
"""

from __future__ import annotations

import pathlib
import sys
import time

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    fast = "--fast" in sys.argv
    from benchmarks import (bench_comm, bench_contention, bench_curves,
                            bench_fig2, bench_kernels, bench_roofline,
                            bench_serve, bench_sweep, bench_table1)
    print("name,us_per_call,derived")
    t0 = time.time()
    for row in bench_comm.run():
        print(row)
    for row in bench_sweep.run(smoke=fast):
        print(row)
    for row in bench_curves.run(smoke=fast):
        print(row)
    # canonical trajectory files only from full-scale runs: a --fast smoke
    # must not overwrite the committed full-scale numbers with smoke ones
    for row in bench_serve.run(
            smoke=fast,
            bench_json_path=None if fast
            else str(REPO_ROOT / "BENCH_serve.json")):
        print(row)
    for row in bench_contention.run(
            smoke=fast,
            json_path=None if fast
            else str(REPO_ROOT / "BENCH_contention.json")):
        print(row)
    for row in bench_kernels.run():
        print(row)
    for row in bench_roofline.run():
        print(row)
    for row in bench_table1.run(steps=120 if fast else 600,
                                seeds=(0,) if fast else (0, 1)):
        print(row)
    for row in bench_fig2.run(steps=60 if fast else 400):
        print(row)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()

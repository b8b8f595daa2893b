"""Run one benchmark cell once on the chips of this machine.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's files are found by its name in
``BENCHMARK.json`` (see ``bench/lib/harness.py``).  Set-up (building the
system under test from ``--seed``, compiling and warming every shape the
window uses) is timed as ``setup_s``; the window then measures for
``--seconds``; after it the outputs the window produced are compared with
the cell's plain reference.  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit);
the same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 2.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def result_line(cell, run, units: dict, tracing: bool) -> dict:
    from bench.lib import harness as H

    metrics = {}
    if tracing:
        for name in cell.per_layer:
            value = H.metric_reader(name)(run, cell)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        for name in cell.end_to_end:
            metrics[name] = {"value": run.e2e[name], "unit": units[name]}
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": run.device}
    if tracing and run.summary is not None:
        line["breakdown"] = run.summary.breakdown()
    line["checks"] = {name: {"value": c.value, "limit": c.limit}
                      for name, c in run.checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no system under test at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    # the TPU runtime's logs go under this run's own temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness as H

    bench = H.benchmark()
    cell = H.resolve(args.workload, bench)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    cache = H.use_compile_cache()
    try:
        device = H.device_info(cell.chips)
    except H.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    H.log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} device {device} cache {cache}")
    clock = H.CompileClock()
    run = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                            tracing=bool(args.trace), t0=T0, clock=clock,
                            device=device)
    line = result_line(cell, run, units, bool(args.trace))
    if run.error:
        H.log(f"check could not be made: {run.error}")
    for text in H.check_lines(run.checks):
        H.log(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's correctness limits: the program's and the
control's, over many seeds, in one process on the chip.

  python bench/calibrate.py --workload <cell> --seconds <s> --seeds <n> ...

For every seed it prints one JSON line ``{"seed", "program"}``, on the
first ``--control-seeds`` seeds with ``control`` (and ``faults`` where the
driver plants them in the reference), with the
numbers the cell's check compares: ``program`` from the system under
test, ``control`` from the cell's plain reference computed one step of
precision below the configuration's and put in the program's place.
The benchmark's runs never run this; the limits in
``bench/workloads/<cell>.json`` are set between the two readings.
"""

import argparse
import json
import os
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=4,
                    help="how many of the seeds also read the control "
                    "(and the planted faults, where the driver has them)")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.lib import harness as H

    cell = H.resolve(args.workload)
    H.use_compile_cache()
    try:
        H.device_info(cell.chips)
    except H.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    for row in cell.driver().calibrate(cell, args.seeds, args.seconds,
                                       args.control_seeds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings from which a cell's limits are set, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--faults half_batch,no_exchange] \\
        [--fault-seeds 1,2,3] [--out FILE]

For each seed, one run of the cell with a window of one block: the numbers
the check compares, sound program against the plain reference.  For each
control seed also the control: the reference computed in fp8 in the
program's place.  For each fault and fault seed, the run with that fault
planted in the timed path.  One JSON line per run, on standard output and
appended to ``--out``, with the compared arrays (per leaf and agent, the
squared norm of the change after the first and the last checked block, of
the program, the reference and the control), so that the limits can be
set from them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
sys.path[0] = str(_ROOT)
sys.path.insert(1, str(_ROOT / "src"))


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from benchmarks.chip.harness import run

    jobs = [(s, "none") for s in args.seeds]
    jobs += [(s, f) for f in args.faults.split(",") if f
             for s in args.fault_seeds]
    for seed, fault in jobs:
        t = time.perf_counter()
        out = run(args.workload, seed, 0.0, False, t_start=t, fault=fault,
                  control=fault == "none" and seed in args.control_seeds,
                  raw=True)
        line = {"workload": args.workload, "seed": seed, "fault": fault,
                "correct": out["correct"], "checks": out["checks"],
                "control": out.get("control"),
                "memory_peak_bytes": out["device"]["memory_peak_bytes"],
                "raw": out["raw"],
                "kind": out["device"]["kind"],
                "seconds": time.perf_counter() - t}
        print("CALIB " + json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

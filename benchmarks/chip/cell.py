#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/cell.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root
of the repository; ``benchmarks/chip/harness.py`` says what a run does.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace.  The last
line of standard output is one JSON object; the numbers compared with the
plain reference, each beside its limit, are the last lines of standard
error.  Exits nonzero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's compilation cache is kept in
``.jax_cache`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[2]
# this directory holds modules named like the standard library's (trace):
# import them as benchmarks.chip.*, never from the script's own directory
sys.path[0] = str(_ROOT)
sys.path.insert(1, str(_ROOT / "src"))
# JAX's persistent compilation cache lives inside the checkout, at a fixed
# path (part of the cache's key), whatever the environment says: only the
# first run of a cell in a checkout compiles, and two checkouts share none
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.chip.harness import NoChip, run
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START)
    except NoChip as e:
        print(f"cell: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

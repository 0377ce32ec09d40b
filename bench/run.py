#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine whose JAX sees the TPU chips the
cell asks for.  The cells are the ``workloads`` of ``BENCHMARK.json``.  The
run makes its inputs on the device from ``--seed``, warms up the cell's
shapes, measures for ``--seconds``, compares what the timed path produced
with a plain reference, and prints one JSON object as the last line of its
standard output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the run is profiled and its metrics are the
per-layer ones.  Where JAX finds no TPU, or fewer chips than the cell asks
for, it exits non-zero and prints no result.

Compiled programs are cached under ``experiments/bench/jax_cache`` in the
checkout; journals and profiles go under ``experiments/bench/<cell>``.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a cell's name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seeds the inputs and the order of the work")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window, report per-layer metrics")
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness.cell import run_cell
    from harness.device import NoAccelerator

    try:
        return run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    except ImportError as e:
        print(f"bench: cannot import the program under test from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 4


if __name__ == "__main__":
    sys.exit(main())

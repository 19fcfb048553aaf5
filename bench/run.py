#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  Without an accelerator it prints no result and exits non-zero.
The last line of standard output is the result, one JSON object; the last
lines of standard error are the compared numbers beside their limits.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The compile cache lives at a fixed path inside the checkout, whatever the
# machine sets, so that two checkouts never share compiled programs.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import repro  # noqa: F401  (the program under test must be present)

    from bench import harness

    return harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                       root=ROOT, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
